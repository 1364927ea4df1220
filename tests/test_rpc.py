"""RPC boundary tests: in-process server/client round-trips, revert
propagation, head subscriptions — and the flagship cross-process test:
the full proposer -> notary period pipeline with the chain in a SEPARATE
OS PROCESS reached only over the wire (the reference's topology,
`sharding/mainchain/utils.go:17-22`)."""

import json
import subprocess
import sys
import time
from pathlib import Path

import pytest

from gethsharding_tpu.actors import Notary, Proposer, TXPool
from gethsharding_tpu.core.types import Transaction
from gethsharding_tpu.node.backend import ShardNode
from gethsharding_tpu.params import Config, ETHER
from gethsharding_tpu.rpc import RemoteMainchain, RPCServer
from gethsharding_tpu.smc.chain import SimulatedMainchain
from gethsharding_tpu.smc.state_machine import SMCRevert
from gethsharding_tpu.utils.hexbytes import Address20

REPO_ROOT = Path(__file__).resolve().parents[1]


def wait_until(predicate, timeout=10.0, step=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(step)
    return predicate()


@pytest.fixture()
def rpc_pair():
    backend = SimulatedMainchain(config=Config(quorum_size=1))
    server = RPCServer(backend)
    server.start()
    remote = RemoteMainchain.dial(*server.address)
    yield backend, remote
    remote.close()
    server.stop()


def test_views_round_trip(rpc_pair):
    backend, remote = rpc_pair
    assert remote.block_number == 0
    assert remote.shard_count() == backend.smc.shard_count
    backend.commit()
    assert remote.block_number == 1
    block = remote.block_by_number(1)
    assert bytes(block.hash) == bytes(backend.blocks[1].hash)
    assert remote.collation_record(0, 1) is None


def test_transactions_and_revert(rpc_pair):
    backend, remote = rpc_pair
    addr = Address20(b"\x11" * 20)
    remote.fund(addr, 2000 * ETHER)
    assert remote.balance_of(addr) == 2000 * ETHER
    receipt = remote.register_notary(addr)
    assert receipt.status == 1
    entry = remote.notary_registry(addr)
    assert entry.deposited and entry.pool_index == 0
    # second deposit reverts — and arrives as SMCRevert, not a generic error
    with pytest.raises(SMCRevert, match="already deposited"):
        remote.register_notary(addr)
    assert remote.transaction_receipt(receipt.tx_hash).status == 1


def test_head_subscription_pushes(rpc_pair):
    backend, remote = rpc_pair
    seen = []
    remote.subscribe_new_head(lambda b: seen.append(b.number))
    backend.commit()
    backend.commit()
    assert wait_until(lambda: len(seen) >= 2)
    assert seen[:2] == [1, 2]


def test_full_period_pipeline_cross_process(tmp_path):
    """test_end_to_end's period pipeline with the mainchain in its own OS
    process: proposer + notary live here, the chain and SMC live in the
    child, and EVERYTHING crosses the JSON-RPC wire — SMC transactions,
    head subscriptions, AND the shardp2p body sync (each node's p2p rides
    its own socket through the chain process's relay)."""
    from gethsharding_tpu.p2p.remote import RemoteHub

    proc = subprocess.Popen(
        [sys.executable, "-m", "gethsharding_tpu.rpc.chain_server",
         "--periodlength", "5", "--quorum", "1", "--runtime", "120"],
        cwd=REPO_ROOT, stdout=subprocess.PIPE, text=True,
    )
    try:
        endpoint = json.loads(proc.stdout.readline())
        config = Config(quorum_size=1)
        chain_ctl = RemoteMainchain.dial(endpoint["host"], endpoint["port"])
        shard_id = 2

        proposer_node = ShardNode(
            actor="proposer", shard_id=shard_id, config=config,
            backend=RemoteMainchain.dial(endpoint["host"], endpoint["port"]),
            hub=RemoteHub.dial(endpoint["host"], endpoint["port"]),
            txpool_interval=None)
        notary_node = ShardNode(
            actor="notary", shard_id=shard_id, config=config,
            backend=RemoteMainchain.dial(endpoint["host"], endpoint["port"]),
            hub=RemoteHub.dial(endpoint["host"], endpoint["port"]),
            deposit=True)
        chain_ctl.fund(notary_node.client.account(), 2000 * ETHER)

        proposer_node.start()
        notary_node.start()
        try:
            notary = notary_node.service(Notary)
            assert notary.is_account_in_notary_pool()

            chain_ctl.fast_forward(1)
            period = chain_ctl.current_period()
            proposer_node.service(TXPool).submit(
                Transaction(nonce=1, payload=b"cross-process tx"))
            assert wait_until(
                lambda: proposer_node.service(Proposer).collations_proposed >= 1
            ), notary_node.errors() + proposer_node.errors()
            # the local counter leads the SMC tx: wait for the chain-side
            # submission too (the bare equality flaked under CPU
            # starvation in full-suite runs)
            assert wait_until(
                lambda: chain_ctl.last_submitted_collation(shard_id) == period,
                timeout=15.0), notary_node.errors() + proposer_node.errors()

            approved = False
            for _ in range(config.period_length - 1):
                chain_ctl.commit()
                if wait_until(
                        lambda: chain_ctl.last_approved_collation(shard_id)
                        == period, timeout=3.0):
                    approved = True
                    break
            assert approved, notary_node.errors() + proposer_node.errors()
            record = chain_ctl.collation_record(shard_id, period)
            assert record.is_elected is True
            assert record.vote_sigs  # the BLS-signed vote crossed the wire
            assert wait_until(lambda: notary.canonical_set >= 1, timeout=5.0)
            # de-starred data plane: every directed body response flowed
            # peer-to-peer over the direct sockets; the chain process
            # relayed ZERO directed sends
            stats = chain_ctl.rpc.call("shard_p2pStats")
            assert stats["relayed_sends"] == 0, stats
        finally:
            notary_node.stop()
            proposer_node.stop()
            chain_ctl.close()
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def _hub_identity(seed: bytes):
    from gethsharding_tpu.mainchain.accounts import AccountManager

    manager = AccountManager()
    account = manager.new_account(seed=seed)
    return manager, account.address


def test_p2p_handshake_and_peer_table():
    """Protocol/version/network gate + PROVEN identity on relay attach
    (the RLPx authenticated-handshake analog, p2p/rlpx.go:178) and the
    admin_peers-style table."""
    import pytest

    from gethsharding_tpu.p2p.remote import RemoteHub
    from gethsharding_tpu.p2p.service import P2PServer
    from gethsharding_tpu.params import Config
    from gethsharding_tpu.rpc.client import RemoteMainchain
    from gethsharding_tpu.rpc.server import RPCServer
    from gethsharding_tpu.smc.chain import SimulatedMainchain

    backend = SimulatedMainchain(config=Config(network_id=77))
    server = RPCServer(backend, port=0)
    server.start()
    try:
        host, port = server.address
        manager, address = _hub_identity(b"peer-table")

        # matching network + proven identity -> attached, listed
        hub = RemoteHub.dial(host, port, network_id=77,
                             accounts=manager, account=address)
        p2p = P2PServer(hub=hub)
        p2p.start()
        chain = RemoteMainchain.dial(host, port)
        assert chain.network_id() == 77
        peers = chain.p2p_peers()
        assert [p["account"] for p in peers] == [bytes(address).hex()]
        assert peers[0]["version"] == 1
        assert peers[0]["endpoint"]  # the direct-listener introduction

        # wrong network -> rejected at attach (before signature checks)
        mgr2, addr2 = _hub_identity(b"wrong-net")
        bad_hub = RemoteHub.dial(host, port, network_id=78,
                                 accounts=mgr2, account=addr2)
        bad_p2p = P2PServer(hub=bad_hub)
        with pytest.raises(Exception, match="network mismatch"):
            bad_p2p.start()
        bad_hub.close()

        # wrong protocol version -> rejected
        worse = RemoteHub.dial(host, port)
        with pytest.raises(Exception, match="version mismatch"):
            worse.rpc.call("shard_p2pAttach", {"protocol": "shardp2p",
                                               "version": 99})
        worse.close()

        # detach drops the peer from the table
        p2p.stop()
        assert chain.p2p_peers() == []
        chain.close()
    finally:
        server.stop()


def test_unsigned_and_forged_attaches_refused():
    """The relay's trust model: `account` is proven by a signature over a
    relay-issued challenge — an unsigned attach, a forged account, and a
    replayed/absent challenge are all refused."""
    import pytest

    from gethsharding_tpu.p2p import direct
    from gethsharding_tpu.p2p.remote import RemoteHub
    from gethsharding_tpu.p2p.service import P2PServer
    from gethsharding_tpu.params import Config
    from gethsharding_tpu.rpc.server import RPCServer
    from gethsharding_tpu.smc.chain import SimulatedMainchain

    backend = SimulatedMainchain(config=Config(network_id=5))
    server = RPCServer(backend, port=0)
    server.start()
    try:
        host, port = server.address
        manager, address = _hub_identity(b"honest")
        thief_mgr, thief_addr = _hub_identity(b"thief")

        # no identity at all -> the client itself refuses to attach
        anon = RemoteHub.dial(host, port)
        with pytest.raises(RuntimeError, match="identity required"):
            P2PServer(hub=anon).start()
        anon.close()

        # unsigned attach straight at the wire -> refused by the relay
        bare = RemoteHub.dial(host, port)
        with pytest.raises(Exception, match="unsigned attach"):
            bare.rpc.call("shard_p2pAttach", {
                "protocol": "shardp2p", "version": 1, "network_id": 5,
                "account": bytes(address).hex()})

        # forged: thief signs with its own key but claims the honest
        # account -> signature does not prove the claim
        challenge = bytes.fromhex(bare.rpc.call("shard_p2pChallenge"))
        sig = thief_mgr.sign_hash(thief_addr, direct.attach_digest(
            5, challenge))
        with pytest.raises(Exception, match="does not prove"):
            bare.rpc.call("shard_p2pAttach", {
                "protocol": "shardp2p", "version": 1, "network_id": 5,
                "account": bytes(address).hex(), "sig": sig.hex()})

        # a correct signature without a FRESH challenge -> refused (the
        # failed attach above consumed it)
        sig = manager.sign_hash(address, direct.attach_digest(5, challenge))
        with pytest.raises(Exception, match="no pending challenge"):
            bare.rpc.call("shard_p2pAttach", {
                "protocol": "shardp2p", "version": 1, "network_id": 5,
                "account": bytes(address).hex(), "sig": sig.hex()})
        bare.close()

        # the honest flow still works
        hub = RemoteHub.dial(host, port, accounts=manager, account=address)
        p2p = P2PServer(hub=hub)
        p2p.start()
        p2p.stop()
    finally:
        server.stop()


def test_directed_messages_flow_peer_to_peer():
    """De-starred data plane: a directed send crosses a direct socket
    between the two actor processes' listeners — the relay sees ZERO
    relayed sends — and a forged direct connection is refused."""
    import socket

    from gethsharding_tpu.p2p import direct
    from gethsharding_tpu.p2p.messages import CollationBodyRequest
    from gethsharding_tpu.p2p.remote import RemoteHub
    from gethsharding_tpu.p2p.service import P2PServer
    from gethsharding_tpu.params import Config
    from gethsharding_tpu.rpc.server import RPCServer
    from gethsharding_tpu.smc.chain import SimulatedMainchain
    from gethsharding_tpu.utils.hexbytes import Hash32

    backend = SimulatedMainchain(config=Config(network_id=9))
    server = RPCServer(backend, port=0)
    server.start()
    try:
        host, port = server.address
        mgr_a, addr_a = _hub_identity(b"alice")
        mgr_b, addr_b = _hub_identity(b"bob")
        hub_a = RemoteHub.dial(host, port, accounts=mgr_a, account=addr_a)
        hub_b = RemoteHub.dial(host, port, accounts=mgr_b, account=addr_b)
        a, b = P2PServer(hub=hub_a), P2PServer(hub=hub_b)
        a.start()
        b.start()
        try:
            sub = b.subscribe(CollationBodyRequest)
            req = CollationBodyRequest(
                shard_id=1, period=2, chunk_root=Hash32(b"\x11" * 32),
                proposer=addr_a)
            assert a.send(req, b.self_peer) is True
            msg = sub.get(timeout=5.0)
            assert msg.data == req
            assert msg.peer == a.self_peer  # reply routing intact
            # ...and the relay never carried it
            assert server.p2p_relayed_sends == 0
            # the connection negotiated AEAD frames (ECDH + AES-256-GCM:
            # the RLPx encrypted-transport parity), not plaintext
            conn = next(iter(hub_a._dialer._conns.values()))
            assert conn[3] is not None
            # reply back over B's own direct connection to A
            sub_a = a.subscribe(CollationBodyRequest)
            assert b.send(req, msg.peer) is True
            assert sub_a.get(timeout=5.0).peer == b.self_peer
            assert server.p2p_relayed_sends == 0

            # forged direct connection: correct wire protocol, but the
            # signature can't prove the account the relay has for peer A
            info = hub_a.peer_info(a.self_peer.peer_id)
            thief_mgr, thief_addr = _hub_identity(b"mallory")
            with socket.create_connection(tuple(
                    hub_b.peer_info(b.self_peer.peer_id)["endpoint"]),
                    timeout=5.0) as sock:
                rfile = sock.makefile("rb")
                wfile = sock.makefile("wb")
                challenge = bytes.fromhex(
                    json.loads(rfile.readline())["challenge"])
                sig = thief_mgr.sign_hash(
                    thief_addr, direct.direct_digest(9, challenge))
                wfile.write((json.dumps({
                    "peer_id": a.self_peer.peer_id,  # claims to be A
                    "account": bytes(addr_a).hex(),
                    "challenge2": bytes(32).hex(),
                    "sig": sig.hex()}) + "\n").encode())
                wfile.flush()
                reply = json.loads(rfile.readline())
            assert "error" in reply and "prove" in reply["error"]
            assert info["account"] == bytes(addr_a).hex()
        finally:
            a.stop()
            b.stop()
    finally:
        server.stop()


def test_gossip_introduction_survives_relay_death():
    """Decentralized introduction (p2p/discovery.py): nodes exchange
    SIGNED announces via gossip over the direct plane; after the relay
    process dies, directed sends AND broadcasts still reach every
    introduced peer — the relay is first contact, not a chokepoint
    (p2p/discover/table.go + p2p/dial.go role; VERDICT r3 Missing #1)."""
    from gethsharding_tpu.p2p.messages import CollationBodyRequest
    from gethsharding_tpu.p2p.remote import RemoteHub
    from gethsharding_tpu.p2p.service import P2PServer
    from gethsharding_tpu.smc.chain import SimulatedMainchain
    from gethsharding_tpu.utils.hexbytes import Hash32

    backend = SimulatedMainchain(config=Config(network_id=11))
    server = RPCServer(backend, port=0)
    server.start()
    host, port = server.address
    hubs, servers = [], []
    try:
        for seed in (b"ga", b"gb", b"gc"):
            mgr, addr = _hub_identity(seed)
            hub = RemoteHub.dial(host, port, accounts=mgr, account=addr)
            srv = P2PServer(hub=hub)
            srv.start()
            hubs.append(hub)
            servers.append(srv)
        a, b, c = servers

        # gossip until everyone holds everyone's VERIFIED announce
        deadline = time.time() + 10.0
        while time.time() < deadline:
            for hub in hubs:
                hub.gossip_once()
            if all(len(hub.directory.gossip_set()) == 3 for hub in hubs):
                break
            time.sleep(0.05)
        assert all(len(hub.directory.gossip_set()) == 3 for hub in hubs)

        # broadcasts while the relay is up already do NOT transit it
        sub_b = b.subscribe(CollationBodyRequest)
        sub_c = c.subscribe(CollationBodyRequest)
        req = CollationBodyRequest(shard_id=3, period=1,
                                   chunk_root=Hash32(b"\x22" * 32),
                                   proposer=None)
        bcasts_before = server.method_calls.get("shard_p2pBroadcast", 0)
        sends_before = server.p2p_relayed_sends
        assert a.broadcast(req) == 2
        assert sub_b.get(timeout=5.0).data == req
        assert sub_c.get(timeout=5.0).data == req
        assert server.method_calls.get(
            "shard_p2pBroadcast", 0) == bcasts_before
        assert server.p2p_relayed_sends == sends_before

        # kill the relay: introduction already happened, the network
        # must keep working peer-to-peer
        server.stop()
        req2 = CollationBodyRequest(shard_id=4, period=2,
                                    chunk_root=Hash32(b"\x33" * 32),
                                    proposer=None)
        assert a.broadcast(req2) == 2
        assert sub_b.get(timeout=5.0).data == req2
        assert sub_c.get(timeout=5.0).data == req2
        # directed body exchange without the relay
        sub_a = a.subscribe(CollationBodyRequest)
        assert b.send(req2, a.self_peer) is True
        assert sub_a.get(timeout=5.0).peer == b.self_peer
    finally:
        for srv in servers:
            srv.stop()
        server.stop()


def test_mirror_snapshot_bulk_over_rpc():
    """A remote actor's state mirror pulls ONE bulk snapshot per head
    instead of ~3 RPC calls per shard."""
    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.mainchain.accounts import AccountManager
    from gethsharding_tpu.mainchain.client import SMCClient
    from gethsharding_tpu.mainchain.mirror import StateMirror
    from gethsharding_tpu.params import Config, ETHER
    from gethsharding_tpu.rpc.client import RemoteMainchain
    from gethsharding_tpu.rpc.server import RPCServer
    from gethsharding_tpu.smc.chain import SimulatedMainchain
    from gethsharding_tpu.utils.hexbytes import Hash32

    config = Config(shard_count=5)
    backend = SimulatedMainchain(config=config)
    manager = AccountManager()
    acct = manager.new_account(seed=b"mirror-rpc")
    backend.fund(acct.address, 2000 * ETHER)
    server = RPCServer(backend, port=0)
    server.start()
    try:
        remote = RemoteMainchain.dial(*server.address)
        client = SMCClient(backend=remote, accounts=manager, account=acct,
                           config=config)
        mirror = StateMirror(client=client)
        mirror.start()
        try:
            backend.fast_forward(1)
            period = backend.current_period()
            root = Hash32(keccak256(b"rpc-mirror"))
            backend.add_header(acct.address, 4, period, root)
            backend.commit()
            import time

            deadline = time.time() + 5.0
            while time.time() < deadline:
                if (mirror.period() == period
                        and mirror.record(4) is not None):
                    break
                time.sleep(0.05)
            assert mirror.period() == period
            assert mirror.record(4)["chunk_root"] == bytes(root).hex()
            assert mirror.snapshot()["last_submitted"][4] == period
        finally:
            mirror.stop()
        remote.close()
    finally:
        server.stop()


def test_remote_notary_hot_loop_is_o1_per_head():
    """The mirror-backed hot loop: a remote notary's per-head read
    chatter is ONE bulk mirrorSnapshot pull, not O(shards) record/
    watermark calls — asserted against the server's per-method counters
    with a 32-shard config."""
    from gethsharding_tpu.actors.notary import Notary
    from gethsharding_tpu.mainchain.mirror import StateMirror

    config = Config(shard_count=32, quorum_size=1)
    backend = SimulatedMainchain(config=config)
    server = RPCServer(backend, port=0)
    server.start()
    node = None
    try:
        remote = RemoteMainchain.dial(*server.address)
        node = ShardNode(actor="notary", backend=remote, config=config,
                         deposit=False, txpool_interval=None)
        backend.fund(node.client.account(), 2000 * ETHER)
        node.client.register_notary()
        node.start()
        notary = node.service(Notary)
        assert node.service(StateMirror) is notary.mirror

        baseline = dict(server.method_calls)
        heads = 3 * config.period_length
        for _ in range(heads):
            backend.commit()
        assert wait_until(
            lambda: (node.service(StateMirror).snapshot() or {}).get(
                "block_number", 0) >= backend.block_number)

        calls = {m: n - baseline.get(m, 0)
                 for m, n in server.method_calls.items()}
        # the O(shards) scan methods never cross the wire per head
        assert calls.get("shard_collationRecord", 0) == 0, calls
        assert calls.get("shard_lastSubmittedCollation", 0) == 0, calls
        assert calls.get("shard_committeeContext", 0) == 0, calls
        assert calls.get("shard_getNotaryInCommittee", 0) == 0, calls
        # the bulk pull happens about once per head (head callback +
        # at most one catch-up refresh from the notary)
        assert calls.get("shard_mirrorSnapshot", 0) <= 2 * heads + 2, calls
        # total per-head chatter is O(1): bounded well under shard_count
        per_head = sum(calls.values()) / heads
        assert per_head < 8, (per_head, calls)
    finally:
        if node is not None:
            node.stop()
        server.stop()


def test_remote_windback_reads_come_from_the_snapshot():
    """Enforced windback over RPC: prior-period records ride the mirror
    snapshot's `prior_records` (closed periods are immutable), so a
    remote notary's windback availability checks cost ZERO extra
    `shard_collationRecord` round trips (r3's O(depth)-RPC gap)."""
    from gethsharding_tpu.actors.notary import Notary
    from gethsharding_tpu.actors.proposer import create_collation
    from gethsharding_tpu.core.types import Transaction
    from gethsharding_tpu.mainchain.mirror import StateMirror

    config = Config(shard_count=2, quorum_size=1, windback_depth=3)
    backend = SimulatedMainchain(config=config)
    server = RPCServer(backend, port=0)
    server.start()
    node = None
    try:
        remote = RemoteMainchain.dial(*server.address)
        node = ShardNode(actor="notary", backend=remote, config=config,
                         deposit=False, txpool_interval=None)
        backend.fund(node.client.account(), 2000 * ETHER)
        node.client.register_notary()
        node.start()
        notary = node.service(Notary)
        shard_id = notary.shard.shard_id
        for period in (1, 2, 3):
            backend.fast_forward(1)
            coll = create_collation(node.client, shard_id, period,
                                    [Transaction(nonce=period)])
            notary.shard.save_collation(coll)
            node.client.add_header(shard_id, period, coll.header.chunk_root,
                                   coll.header.proposer_signature)
        backend.commit()
        assert wait_until(
            lambda: (node.service(StateMirror).snapshot() or {}).get(
                "period") == 3)
        snap = node.service(StateMirror).snapshot()
        assert set(snap["prior_records"]) == {1, 2}, snap["prior_records"]

        baseline = dict(server.method_calls)
        checks_before = notary.m_windback_checks.value
        notary.notarize_collations()
        calls = {m: n - baseline.get(m, 0)
                 for m, n in server.method_calls.items()}
        # windback DID run (periods 1-2 were checked for availability)...
        assert notary.m_windback_checks.value >= checks_before + 2
        # ...and no per-period record read crossed the wire for it
        assert calls.get("shard_collationRecord", 0) == 0, calls
        assert notary.votes_submitted >= 1
    finally:
        if node is not None:
            node.stop()
        server.stop()


def test_bootnode_introduction_without_a_chain():
    """cmd/bootnode parity: a chainless introduction node serves the
    authenticated peer table and the direct data plane works through it,
    while every chain/SMC method is refused."""
    from gethsharding_tpu.p2p.messages import CollationBodyRequest
    from gethsharding_tpu.p2p.remote import RemoteHub
    from gethsharding_tpu.p2p.service import P2PServer
    from gethsharding_tpu.rpc.bootnode import make_bootnode
    from gethsharding_tpu.utils.hexbytes import Hash32

    server = make_bootnode(network_id=12)
    server.start()
    try:
        host, port = server.address
        mgr_a, addr_a = _hub_identity(b"boot-a")
        mgr_b, addr_b = _hub_identity(b"boot-b")
        hub_a = RemoteHub.dial(host, port, accounts=mgr_a, account=addr_a)
        hub_b = RemoteHub.dial(host, port, accounts=mgr_b, account=addr_b)
        a, b = P2PServer(hub=hub_a), P2PServer(hub=hub_b)
        a.start()
        b.start()
        try:
            assert hub_a.rpc.call("shard_networkId") == 12
            sub = b.subscribe(CollationBodyRequest)
            req = CollationBodyRequest(shard_id=0, period=1,
                                       chunk_root=Hash32(b"\x22" * 32),
                                       proposer=addr_a)
            assert a.send(req, b.self_peer) is True
            assert sub.get(timeout=5.0).data == req
            assert server.p2p_relayed_sends == 0  # payload went direct
            # chain methods are refused, not silently faked
            with pytest.raises(Exception, match="chain process"):
                hub_a.rpc.call("shard_blockNumber")
        finally:
            a.stop()
            b.stop()
    finally:
        server.stop()


class _ThreadTellingServer(RPCServer):
    """Two methods that say which thread served them."""

    def rpc_threadIdent(self):
        import threading

        return threading.get_ident()

    def rpc_heldThreadIdent(self, seconds):
        import threading

        time.sleep(seconds)
        return threading.get_ident()


def test_a_connections_workers_outlive_their_requests():
    """Requests that follow one another on a connection run on ONE
    thread (ISSUE 35: a new thread a request paid 230 ms of malloc arena
    growth on every 14 MB frame); requests in flight together run on a
    thread each, which later requests then find idle; a blank line is no
    request; closing the connection ends its workers."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from gethsharding_tpu.rpc.client import RPCClient

    def all_workers():
        return {t.ident for t in threading.enumerate()
                if t.name == "rpc-conn-worker"}

    # an earlier test's connection that is still open keeps its workers
    others = all_workers()

    def conn_workers():
        return sorted(all_workers() - others)

    server = _ThreadTellingServer(SimulatedMainchain())
    server.start()
    try:
        client = RPCClient(*server.address)
        client._file.write(b" \n")      # a blank line between frames
        client._file.flush()
        serial = {client.call("shard_threadIdent") for _ in range(5)}
        assert len(serial) == 1
        with ThreadPoolExecutor(3) as pool:
            held = list(pool.map(
                lambda _: client.call("shard_heldThreadIdent", 0.3),
                range(3)))
        assert len(set(held)) == 3 and serial <= set(held)
        assert conn_workers() == sorted(held)
        # all three are idle now: nothing is spawned for what follows
        assert {client.call("shard_threadIdent")
                for _ in range(5)} <= set(held)
        assert conn_workers() == sorted(held)
        other = RPCClient(*server.address)
        assert other.call("shard_threadIdent") not in held
        other.close()
        client.close()
        assert wait_until(lambda: conn_workers() == [])
    finally:
        server.stop()
