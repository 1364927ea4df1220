"""ShardNode service container + CLI."""

import pytest

from gethsharding_tpu.actors import Notary, Observer, Proposer, Simulator, Syncer, TXPool
from gethsharding_tpu.db.shard_db import ShardDB
from gethsharding_tpu.mainchain.client import SMCClient
from gethsharding_tpu.node.backend import ShardNode
from gethsharding_tpu.node.cli import build_parser
from gethsharding_tpu.p2p.service import Hub, P2PServer
from gethsharding_tpu.params import Config, ETHER
from gethsharding_tpu.smc.chain import SimulatedMainchain


def test_registry_composition_per_actor():
    backend = SimulatedMainchain()
    proposer_node = ShardNode(actor="proposer", backend=backend,
                              txpool_interval=None)
    assert isinstance(proposer_node.service(Proposer), Proposer)
    assert isinstance(proposer_node.service(TXPool), TXPool)
    assert isinstance(proposer_node.service(Simulator), Simulator)
    with pytest.raises(KeyError):
        proposer_node.service(Notary)

    notary_node = ShardNode(actor="notary", backend=backend)
    assert isinstance(notary_node.service(Notary), Notary)
    with pytest.raises(KeyError):
        notary_node.service(Simulator)  # notaries don't run the simulator

    observer_node = ShardNode(actor="observer", backend=backend)
    assert isinstance(observer_node.service(Observer), Observer)
    assert isinstance(observer_node.service(Syncer), Syncer)


def test_unknown_actor_rejected():
    with pytest.raises(ValueError, match="unknown actor"):
        ShardNode(actor="validator")


def test_start_stop_lifecycle():
    backend = SimulatedMainchain()
    node = ShardNode(actor="observer", backend=backend,
                     simulator_interval=0.05)
    node.start()
    assert node.service(Syncer).running
    node.stop()
    assert not node.service(Syncer).running
    assert node.errors() == []


def test_nodes_share_hub_and_backend():
    config = Config(quorum_size=1)
    backend = SimulatedMainchain(config=config)
    hub = Hub()
    a = ShardNode(actor="proposer", shard_id=0, config=config,
                  backend=backend, hub=hub, txpool_interval=None)
    b = ShardNode(actor="notary", shard_id=0, config=config,
                  backend=backend, hub=hub)
    assert a.client.backend is b.client.backend
    assert a.p2p.hub is b.p2p.hub


def test_cli_parser_flags():
    parser = build_parser()
    args = parser.parse_args(
        ["sharding", "--actor", "notary", "--shardid", "7", "--deposit",
         "--runtime", "2"]
    )
    assert args.actor == "notary"
    assert args.shardid == 7
    assert args.deposit is True
    with pytest.raises(SystemExit):
        parser.parse_args(["sharding", "--actor", "miner"])


def test_supervisor_restarts_crashed_service_as_fresh_instance():
    """Failure detection + elastic recovery: a crashed actor loop is
    replaced by a FRESH instance (node/service.go:78-83 restart
    semantics), bounded by MAX_RESTARTS."""
    import time

    from gethsharding_tpu.actors.syncer import Syncer
    from gethsharding_tpu.smc.chain import SimulatedMainchain

    node = ShardNode(actor="observer", backend=SimulatedMainchain(),
                     txpool_interval=None, supervise=True,
                     supervise_interval=0.05)
    node.start()
    try:
        victim = node.service(Syncer)
        assert victim.running and not victim.crashed

        # simulate a loop crash: a spawned thread that raises
        victim.spawn(lambda: (_ for _ in ()).throw(RuntimeError("boom")),
                     name="crash-loop")
        deadline = time.time() + 5.0
        while time.time() < deadline:
            fresh = node.service(Syncer)
            if fresh is not victim:
                break
            time.sleep(0.02)
        fresh = node.service(Syncer)
        assert fresh is not victim, "supervisor must replace the instance"
        assert fresh.running and not fresh.crashed
        assert node.restarts["syncer"] == 1
        assert node.supervisor.restarts_performed >= 1
        # crash history carried forward for observability
        assert any("crashed" in e for e in fresh.errors)
    finally:
        node.stop()


def test_supervisor_gives_up_after_max_restarts():
    import time

    from gethsharding_tpu.actors.syncer import Syncer
    from gethsharding_tpu.smc.chain import SimulatedMainchain

    node = ShardNode(actor="observer", backend=SimulatedMainchain(),
                     txpool_interval=None, supervise=True,
                     supervise_interval=0.02)
    node.start()
    try:
        # every fresh instance crashes immediately: patch the factory
        real_factory = node._factories[Syncer]

        def crashing_factory():
            service = real_factory()
            orig = service.on_start

            def bad_start():
                orig()
                service.spawn(lambda: (_ for _ in ()).throw(
                    RuntimeError("systemic")), name="crash-loop")

            service.on_start = bad_start
            return service

        node._factories[Syncer] = crashing_factory
        node.service(Syncer).spawn(
            lambda: (_ for _ in ()).throw(RuntimeError("first")),
            name="crash-loop")
        deadline = time.time() + 6.0
        while time.time() < deadline:
            if node.restarts.get("syncer", 0) >= node.MAX_RESTARTS:
                break
            time.sleep(0.02)
        time.sleep(0.3)  # a few more supervisor passes
        assert node.restarts["syncer"] == node.MAX_RESTARTS  # capped
        # budget exhausted: the final crashed instance is left DOWN, not
        # half-alive (threads/subscriptions stopped)
        assert not node.service(Syncer).running
        # the give-up is STICKY: even after the restart timestamps age
        # out of RESTART_WINDOW, a systemically broken service stays down
        node._restart_times["syncer"] = []
        assert "syncer" not in node.heal()
        assert not node.service(Syncer).running
    finally:
        node.stop()


def test_consecutive_callback_failures_mark_crashed():
    """Head-driven actors have no loop threads; a run of consecutive
    callback failures marks them crashed for the supervisor."""
    from gethsharding_tpu.actors.base import Service

    class Flaky(Service):
        name = "flaky"
        supervisable = True

    service = Flaky()
    for _ in range(Service.FAILURE_THRESHOLD - 1):
        service.record_failure("boom")
    assert not service.crashed
    service.record_success()  # a success resets the run
    for _ in range(Service.FAILURE_THRESHOLD - 1):
        service.record_failure("boom")
    assert not service.crashed
    service.record_failure("boom")
    assert service.crashed


def test_state_mirror_tracks_and_resumes():
    """Downloader-analog: the mirror snapshots SMC state per head, serves
    local reads, persists to the shard DB, and a fresh instance over the
    same DB warm-starts from the snapshot before any head arrives."""
    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.db.kv import MemoryKV
    from gethsharding_tpu.mainchain.accounts import AccountManager
    from gethsharding_tpu.mainchain.client import SMCClient
    from gethsharding_tpu.mainchain.mirror import StateMirror
    from gethsharding_tpu.params import Config, ETHER
    from gethsharding_tpu.smc.chain import SimulatedMainchain
    from gethsharding_tpu.utils.hexbytes import Hash32

    config = Config(shard_count=4)
    chain = SimulatedMainchain(config=config)
    manager = AccountManager()
    acct = manager.new_account(seed=b"mirror")
    chain.fund(acct.address, 2000 * ETHER)
    client = SMCClient(backend=chain, accounts=manager, account=acct,
                       config=config)
    db = MemoryKV()
    mirror = StateMirror(client=client, shard_db=db)
    mirror.start()
    try:
        assert mirror.refreshes >= 1  # initial refresh at start
        chain.fast_forward(1)
        period = chain.current_period()
        root = Hash32(keccak256(b"mirror-root"))
        chain.add_header(acct.address, 2, period, root)
        chain.commit()  # head -> refresh
        snap = mirror.snapshot()
        assert snap["period"] == period
        assert snap["last_submitted"][2] == period
        assert mirror.record(2)["chunk_root"] == bytes(root).hex()
        assert mirror.record(2)["vote_count"] == 0
        assert mirror.record(0) is None
        assert snap["committee_context"] is not None
    finally:
        mirror.stop()

    # a new instance over the same DB resumes before any head
    cold = StateMirror(client=client, shard_db=db)
    assert cold.resumed_from_disk
    assert cold.record(2)["chunk_root"] == bytes(root).hex()
    assert cold.period() == period

    # without a DB: cold start, no resume
    assert not StateMirror(client=client).resumed_from_disk


def test_state_mirror_tolerates_none_block_number():
    """A backend surfacing block_number=None must not TypeError the
    regression guard; None compares as 0."""
    from gethsharding_tpu.mainchain.mirror import StateMirror

    class Stub:
        def __init__(self):
            self.calls = 0

        def mirror_snapshot(self):
            self.calls += 1
            return {"block_number": 5 if self.calls == 1 else None,
                    "period": 1, "records": {}, "last_submitted": {},
                    "committee_context": None}

    mirror = StateMirror(client=Stub())
    first = mirror.refresh()
    assert first["block_number"] == 5
    # a later None-numbered snapshot never regresses the held one
    assert mirror.refresh() is first


def test_node_runs_a_state_mirror():
    from gethsharding_tpu.mainchain.mirror import StateMirror
    from gethsharding_tpu.node.backend import ShardNode
    from gethsharding_tpu.smc.chain import SimulatedMainchain

    backend = SimulatedMainchain()
    node = ShardNode(actor="observer", backend=backend, txpool_interval=None)
    node.start()
    try:
        mirror = node.service(StateMirror)
        backend.commit()
        assert mirror.snapshot() is not None
        assert mirror.period() == backend.current_period()
    finally:
        node.stop()
