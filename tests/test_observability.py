"""HTTP status endpoint + console REPL (dashboard/console analogs),
span tracing (gethsharding_tpu/tracing), and the Prometheus exposition
surface."""

import io
import json
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from gethsharding_tpu import tracing
from gethsharding_tpu.node.backend import ShardNode
from gethsharding_tpu.smc.chain import SimulatedMainchain


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5) as resp:
        return resp.status, json.loads(resp.read())


@pytest.fixture
def tracer():
    """Enabled process tracer, reset afterwards (module-global state)."""
    tracing.enable(ring_spans=65536)
    tracing.TRACER.clear()
    yield tracing.TRACER
    tracing.disable()
    tracing.TRACER.clear()


def test_status_endpoint_serves_health_metrics_status():
    node = ShardNode(actor="observer", backend=SimulatedMainchain(),
                     txpool_interval=None, http_port=0)
    node.start()
    try:
        from gethsharding_tpu.node.http_status import StatusServer

        port = node.service(StatusServer).port
        code, health = _get(port, "/healthz")
        assert code == 200
        assert health["status"] == "ok"
        assert health["services"]["syncer"] == "running"

        code, status = _get(port, "/status")
        assert code == 200
        assert status["actor"] == "observer"
        assert status["period"] == 0
        assert status["account"].startswith("0x")

        code, metrics = _get(port, "/metrics")
        assert code == 200
        assert isinstance(metrics, dict)

        # unknown path -> 404
        try:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope", timeout=5)
            raise AssertionError("expected 404")
        except urllib.error.HTTPError as exc:
            assert exc.code == 404
    finally:
        node.stop()


def test_status_endpoint_reports_degraded_on_crash():
    from gethsharding_tpu.actors.syncer import Syncer
    from gethsharding_tpu.node.http_status import StatusServer

    node = ShardNode(actor="observer", backend=SimulatedMainchain(),
                     txpool_interval=None, http_port=0)
    node.start()
    try:
        victim = node.service(Syncer)
        victim.spawn(lambda: (_ for _ in ()).throw(RuntimeError("boom")),
                     name="crash")
        import time

        deadline = time.time() + 3.0
        while time.time() < deadline and not victim.crashed:
            time.sleep(0.02)
        port = node.service(StatusServer).port
        _, health = _get(port, "/healthz")
        assert health["status"] == "degraded"
        assert health["services"]["syncer"] == "crashed"
    finally:
        node.stop()


def test_console_drives_a_chain_over_rpc():
    """Console commands against a real chain process over a socket."""
    from gethsharding_tpu.console import ShardingConsole
    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.mainchain.accounts import AccountManager
    from gethsharding_tpu.params import ETHER
    from gethsharding_tpu.rpc.client import RemoteMainchain
    from gethsharding_tpu.rpc.server import RPCServer
    from gethsharding_tpu.utils.hexbytes import Hash32

    backend = SimulatedMainchain()
    server = RPCServer(backend, port=0)
    server.start()
    try:
        manager = AccountManager()
        acct = manager.new_account(seed=b"console")
        backend.fund(acct.address, 2000 * ETHER)
        backend.register_notary(
            acct.address, bls_pubkey=acct.bls_pubkey,
            bls_pop=manager.bls_proof_of_possession(acct.address))
        backend.fast_forward(1)
        root = Hash32(keccak256(b"console-root"))
        period = backend.current_period()
        backend.add_header(acct.address, 3, period, root)
        # one signed vote so the audit command has an auditable shard
        from gethsharding_tpu.smc.state_machine import vote_digest

        backend.submit_vote(
            acct.address, 3, period, 0, root,
            bls_sig=manager.bls_sign(acct.address,
                                     bytes(vote_digest(3, period, root))))

        chain = RemoteMainchain.dial(*server.address)
        addr_hex = "0x" + bytes(acct.address).hex()
        script = "\n".join([
            "block", "period", "shards",
            f"balance {addr_hex}",
            f"registry {addr_hex}",
            "record 3",
            "record 99",
            "votes 3",
            "submitted 3",
            "audit 1",
            "commit",
            "fastforward 2",
            "bogus-command",
            "record not-a-number",
            "quit",
        ]) + "\n"
        out = io.StringIO()
        console = ShardingConsole(chain, stdin=io.StringIO(script),
                                  stdout=out)
        console.cmdloop()
        chain.close()
        text = out.getvalue()
        assert f"{backend.config.shard_count}" in text
        assert "pool_index=0" in text
        assert "chunk_root=0x" + bytes(root).hex() in text
        assert "no record" in text
        # the tally audit over the bulk auditData pull
        assert "period 1 shard 3: votes=1 signed=1 elected=False" in text
        assert "1 shards audited, consistent" in text
        assert "block 6" in text      # commit mined block 6 (period 1 + 1)
        assert "error:" in text       # bad args answered, session survived
        # the two dev commands really advanced the remote chain
        assert backend.current_period() == 3
    finally:
        server.stop()


def test_cli_attach_subcommand_end_to_end():
    """`tpu-sharding attach` as a real subprocess against a chain-server
    subprocess — the full operator flow across two OS processes."""
    chain_proc = subprocess.Popen(
        [sys.executable, "-m", "gethsharding_tpu.rpc.chain_server",
         "--port", "0", "--runtime", "30"],
        stdout=subprocess.PIPE, text=True)
    try:
        info = json.loads(chain_proc.stdout.readline())
        out = subprocess.run(
            [sys.executable, "-m", "gethsharding_tpu.node.cli", "attach",
             "--port", str(info["port"])],
            input="period\ncommit\nquit\n", text=True,
            capture_output=True, timeout=30)
        assert out.returncode == 0
        assert "block 1" in out.stdout
    finally:
        chain_proc.terminate()
        chain_proc.wait(timeout=10)


def test_key_tool_roundtrip(tmp_path):
    """ethkey analog: new -> list -> inspect over the CLI."""
    from gethsharding_tpu.node.cli import run_cli

    ks = str(tmp_path / "keystore")
    pw = tmp_path / "pw"
    pw.write_text("secret\n")
    assert run_cli(["key", "new", "--keystore", ks,
                    "--password", str(pw)]) == 0
    from gethsharding_tpu.mainchain.keystore import Keystore

    accounts = Keystore(ks).accounts()
    assert len(accounts) == 1
    assert run_cli(["key", "list", "--keystore", ks]) == 0
    assert run_cli(["key", "inspect", "--keystore", ks,
                    "--address", accounts[0].address.hex_str,
                    "--password", str(pw)]) == 0
    # wrong password -> clean failure
    bad = tmp_path / "bad"
    bad.write_text("wrong")
    assert run_cli(["key", "inspect", "--keystore", ks,
                    "--address", accounts[0].address.hex_str,
                    "--password", str(bad)]) == 1


def test_rlpdump_tool(capsys):
    from gethsharding_tpu.node.cli import run_cli
    from gethsharding_tpu.utils.rlp import rlp_encode

    blob = rlp_encode([b"cat", [b"dog", b""], b"\x01\x02"])
    assert run_cli(["rlpdump", blob.hex()]) == 0
    out = capsys.readouterr().out
    assert '"cat"' in out and '"dog"' in out and "0x0102" in out
    assert run_cli(["rlpdump", "zz-not-hex"]) == 1
    assert run_cli(["rlpdump", "c1"]) == 1  # truncated list payload


def test_dashboard_page_served_at_root():
    """The dashboard role (dashboard/dashboard.go): GET / returns the
    self-contained live page wired to the three JSON endpoints."""
    from gethsharding_tpu.node.http_status import StatusServer

    node = ShardNode(actor="observer", backend=SimulatedMainchain(),
                     txpool_interval=None, http_port=0)
    node.start()
    try:
        port = node.service(StatusServer).port
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/", timeout=5) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/html")
            page = resp.read().decode()
        for needle in ("/healthz", "/status", "/metrics", "<script>"):
            assert needle in page
    finally:
        node.stop()


def test_faucet_tool_drips_funds():
    """cmd/faucet analog: the CLI faucet funds an address on a running
    chain process over RPC."""
    from gethsharding_tpu.node.cli import run_cli
    from gethsharding_tpu.rpc.server import RPCServer

    backend = SimulatedMainchain()
    server = RPCServer(backend, port=0)
    server.start()
    try:
        addr = "0x" + "ab" * 20
        rc = run_cli(["faucet", "--port", str(server.address[1]),
                      "--address", addr, "--amount", "7"])
        assert rc == 0
        from gethsharding_tpu.params import ETHER
        from gethsharding_tpu.utils.hexbytes import Address20

        assert backend.balance_of(Address20(bytes.fromhex("ab" * 20))) \
            == 7 * ETHER
        assert run_cli(["faucet", "--port", str(server.address[1]),
                        "--address", "nonsense"]) == 1
    finally:
        server.stop()


def test_console_trace_and_python_mode():
    """The trace command prints a tx's event-level execution trace, and
    `py` drops into a scriptable Python REPL with the chain bound (the
    JS-REPL scripting role) — across two real OS processes."""
    chain_proc = subprocess.Popen(
        [sys.executable, "-m", "gethsharding_tpu.rpc.chain_server",
         "--port", "0", "--runtime", "60"],
        stdout=subprocess.PIPE, text=True)
    try:
        info = json.loads(chain_proc.stdout.readline())

        # produce a traceable tx through the remote surface
        from gethsharding_tpu.mainchain.accounts import AccountManager
        from gethsharding_tpu.params import ETHER
        from gethsharding_tpu.rpc.client import RemoteMainchain

        manager = AccountManager()
        acct = manager.new_account(seed=b"trace-console")
        remote = RemoteMainchain.dial("127.0.0.1", info["port"])
        remote.fund(acct.address, 2000 * ETHER)
        receipt = remote.register_notary(acct.address)
        tx_hex = "0x" + bytes(receipt.tx_hash).hex()
        trace = remote.trace_transaction(receipt.tx_hash)
        assert trace["status"] == 1
        assert trace["trace"][0]["event"] == "NotaryRegistered"
        assert trace["trace"][0]["args"]["notary"] == \
            "0x" + bytes(acct.address).hex()
        remote.close()

        script = "\n".join([
            f"trace {tx_hex}",
            "trace 0x" + "ee" * 32,
            "py",
            "print('PYMODE', chain.block_number, binding.shardCount())",
            "exit()",
            "period",  # proves exit() RETURNED to the sharding prompt
            "quit",
        ]) + "\n"
        out = subprocess.run(
            [sys.executable, "-m", "gethsharding_tpu.node.cli", "attach",
             "--port", str(info["port"])],
            input=script, text=True, capture_output=True, timeout=30)
        assert out.returncode == 0
        assert "NotaryRegistered" in out.stdout
        assert "unknown transaction" in out.stdout
        assert "PYMODE 0 100" in out.stdout
        # the console survived exit(): the period command ran after it
        # and printed its value (0) back at the sharding prompt
        assert "> 0\n" in out.stdout[out.stdout.index("PYMODE"):]
    finally:
        chain_proc.terminate()
        chain_proc.wait(timeout=10)


# == span tracing (gethsharding_tpu/tracing) ===============================


def _garbage_rows(i):
    """One cheap serving row (invalid sig recovers to None instantly)."""
    return [bytes([i]) * 32], [bytes([i]) * 65]


def _serving_backend(flush_us=2000.0):
    from gethsharding_tpu.serving import ServingConfig, ServingSigBackend
    from gethsharding_tpu.sigbackend import get_backend

    return ServingSigBackend(get_backend("python"),
                             ServingConfig(flush_us=flush_us))


def test_serving_request_spans_decompose_to_parent(tracer, tmp_path):
    """THE attribution contract: every coalesced request's parent span
    decomposes into queue_wait / batch_assembly / device_dispatch child
    spans summing (±5%) to the parent — in the tracer AND in the
    exported Chrome trace-event JSON."""
    serving = _serving_backend()
    clients = 4
    try:
        def client(c):
            with tracing.span("client/request", client=c):
                serving.ecrecover_addresses(*_garbage_rows(c))

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        serving.close()

    spans = tracer.recent_spans()
    requests = [s for s in spans
                if s["name"] == "serving/ecrecover/request"]
    assert len(requests) == clients
    by_parent = {}
    for s in spans:
        by_parent.setdefault(s["parent"], []).append(s)
    phase_names = {"serving/ecrecover/queue_wait",
                   "serving/ecrecover/batch_assembly",
                   "serving/ecrecover/device_dispatch"}
    for req in requests:
        kids = [s for s in by_parent.get(req["span"], [])
                if s["name"] in phase_names]
        assert {k["name"] for k in kids} == phase_names
        parent_dur = req["end"] - req["start"]
        kids_dur = sum(k["end"] - k["start"] for k in kids)
        assert abs(kids_dur - parent_dur) <= 0.05 * parent_dur
        # the caller's span parents the request (trace propagation
        # through submit() across three threads)
        client_spans = [s for s in spans if s["name"] == "client/request"
                        and s["trace"] == req["trace"]]
        assert len(client_spans) == 1
        assert req["parent"] == client_spans[0]["span"]
        # the caller-side wake phase rides the same trace
        wakes = [s for s in by_parent.get(req["span"], [])
                 if s["name"] == "serving/ecrecover/future_wake"]
        assert len(wakes) == 1

    # the same contract must hold in the exported Chrome trace
    path = str(tmp_path / "trace.json")
    assert tracing.write_chrome_trace(path) == len(spans)
    payload = json.load(open(path))
    # the merge anchor rides every export (scripts/trace_merge.py)
    assert "clock_offset_us" in payload["otherData"]
    events = [e for e in payload["traceEvents"] if e["ph"] != "M"]
    assert all(e["ph"] == "X" for e in events)
    for req in (e for e in events
                if e["name"] == "serving/ecrecover/request"):
        kids = [e for e in events
                if e["args"]["parent_id"] == req["args"]["span_id"]
                and e["name"] in phase_names]
        assert len(kids) == 3
        assert abs(sum(k["dur"] for k in kids) - req["dur"]) \
            <= 0.05 * req["dur"]

    # span durations fed the metrics registry (timers the influx
    # exporter and dashboard pick up for free)
    from gethsharding_tpu.metrics import DEFAULT_REGISTRY

    timer = DEFAULT_REGISTRY.get("trace/serving/ecrecover/request")
    assert timer is not None and timer.count >= clients


def _over_serving(serving, wrappers):
    from gethsharding_tpu.resilience.breaker import FailoverSigBackend
    from gethsharding_tpu.resilience.soundness import SpotCheckSigBackend

    backend = serving
    for wrapper in wrappers:
        backend = (FailoverSigBackend(backend) if wrapper == "failover"
                   else SpotCheckSigBackend(backend, rate=1.0, rows=1))
    return backend


@pytest.mark.parametrize("wrappers", [
    (), ("failover",), ("spot_check",), ("spot_check", "failover")],
    ids=lambda w: "+".join(w) or "bare")
def test_future_wake_is_recorded_through_the_wrapped_futures(
        tracer, wrappers):
    """The notary's recover phase: `submit` on whatever `--serving
    --sigbackend failover-* --soundness-rate` stacked over the serving
    tier, `result()`, then `observe_future_wake` on the wrapper's own
    future, which has `__slots__` and only reads through to the serving
    future. The wake span lands under the request's, and nothing the
    request held waits for the collector once the caller lets go."""
    import gc

    from gethsharding_tpu.serving.batcher import observe_future_wake
    from gethsharding_tpu.serving.queue import Request

    serving = _serving_backend()
    backend = _over_serving(serving, wrappers)
    gc.collect()
    gc.set_debug(gc.DEBUG_SAVEALL)     # what a collection finds, it keeps
    try:
        for i in range(2):  # the tier's threads hold on to their last
            with tracing.span("notary/recover"):
                future = backend.submit("ecrecover_addresses",
                                        *_garbage_rows(i))
                assert len(future.result()) == 1
                observe_future_wake(future)
            del future
        gc.collect()
        loops = [o for o in gc.garbage if isinstance(o, Request)]
    finally:
        gc.set_debug(0)
        gc.garbage.clear()
        serving.close()
    assert loops == []
    spans = tracer.recent_spans()
    requests = {s["span"]: s for s in spans
                if s["name"] == "serving/ecrecover/request"}
    wakes = [s for s in spans if s["name"] == "serving/ecrecover/future_wake"]
    assert len(requests) == len(wakes) == 2
    for wake in wakes:
        request = requests[wake["parent"]]
        assert wake["trace"] == request["trace"]
        assert wake["start"] == request["end"]
        assert wake["tags"]["klass"] == request["tags"]["klass"]


def test_failed_dispatch_still_emits_error_tagged_spans(tracer):
    """Errored requests are the ones most worth attributing: a batch
    whose device call raises still emits its request span tree, tagged
    with the error, before the futures fail."""
    from gethsharding_tpu.serving import ServingConfig, ServingSigBackend

    class BoomBackend:
        name = "boom"

        def ecrecover_addresses(self, digests, sigs65):
            raise RuntimeError("device on fire")

    serving = ServingSigBackend(BoomBackend(), ServingConfig(flush_us=500))
    try:
        with pytest.raises(RuntimeError, match="device on fire"):
            serving.ecrecover_addresses(*_garbage_rows(1))
    finally:
        serving.close()
    requests = [s for s in tracer.recent_spans()
                if s["name"] == "serving/ecrecover/request"]
    assert len(requests) == 1
    assert "device on fire" in requests[0]["tags"]["error"]


def test_tracer_off_overhead_on_serving_hot_path():
    """Tracer-off overhead budget: the guards the serving hot path
    evaluates per request when tracing is disabled must cost <2% of a
    request's serving latency."""
    assert not tracing.TRACER.enabled
    serving = _serving_backend(flush_us=500.0)
    try:
        serving.ecrecover_addresses(*_garbage_rows(0))  # warm the threads
        n = 100
        t0 = time.perf_counter()
        for i in range(n):
            serving.ecrecover_addresses(*_garbage_rows(i % 251))
        per_request_s = (time.perf_counter() - t0) / n
    finally:
        serving.close()

    # the disabled-path work per request: request_context() at submit
    # plus TRACER.enabled reads on the flusher/dispatch/await sides —
    # charge 6 guard evaluations per request (3x the real count)
    m = 100_000
    t0 = perf = time.perf_counter()
    for _ in range(m):
        tracing.request_context()
    guard_s = (time.perf_counter() - perf) / m
    overhead = 6 * guard_s
    assert overhead < 0.02 * per_request_s, (
        f"tracer-off overhead {overhead * 1e6:.3f}us vs request "
        f"{per_request_s * 1e6:.1f}us")


def test_trace_endpoint_and_prometheus_exposition(tracer):
    """/trace serves recent traces; /metrics?format=prom serves the
    Prometheus text exposition; both on the node status server."""
    serving = _serving_backend()
    try:
        serving.ecrecover_addresses(*_garbage_rows(7))
    finally:
        serving.close()
    node = ShardNode(actor="observer", backend=SimulatedMainchain(),
                     txpool_interval=None, http_port=0)
    node.start()
    try:
        from gethsharding_tpu.node.http_status import StatusServer

        port = node.service(StatusServer).port
        code, payload = _get(port, "/trace")
        assert code == 200 and payload["enabled"] is True
        names = {span["name"] for trace in payload["traces"]
                 for span in trace["spans"]}
        assert "serving/ecrecover/request" in names
        assert "serving/ecrecover/device_dispatch" in names

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics?format=prom",
                timeout=5) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
        assert "# TYPE" in text
        assert "gethsharding_serving_ecrecover_requests_total" in text
        # span-duration timers folded into the registry ride the scrape
        assert "gethsharding_trace_serving_ecrecover_request" in text

        # plain /metrics stays JSON
        code, snapshot = _get(port, "/metrics")
        assert code == 200 and isinstance(snapshot, dict)
    finally:
        node.stop()


def test_rpc_response_carries_trace_id(tracer):
    """The RPC server parents serving spans under a handler span and
    returns the trace id on the response envelope."""
    import socket

    from gethsharding_tpu.rpc.server import RPCServer

    server = RPCServer(SimulatedMainchain())
    server.start()
    try:
        sock = socket.create_connection(server.address, timeout=5)
        fh = sock.makefile("rw")
        digest, sig = _garbage_rows(9)
        fh.write(json.dumps({
            "jsonrpc": "2.0", "id": 1, "method": "shard_ecrecover",
            "params": [["0x" + digest[0].hex()], ["0x" + sig[0].hex()]],
        }) + "\n")
        fh.flush()
        response = json.loads(fh.readline())
        assert response["result"] == [None]
        assert isinstance(response["trace"], int)
        sock.close()
        # the handler span and the serving request share one trace
        spans = tracer.recent_spans()
        rpc_spans = [s for s in spans if s["name"] == "rpc/shard_ecrecover"]
        assert len(rpc_spans) == 1
        assert rpc_spans[0]["trace"] == response["trace"]
        request = [s for s in spans
                   if s["name"] == "serving/ecrecover/request"][0]
        assert request["trace"] == response["trace"]
        wake = [s for s in spans
                if s["name"] == "serving/ecrecover/future_wake"]
        assert wake, "RPC handler must record the future_wake phase"
    finally:
        server.stop()


def test_jax_compile_cache_shape_tracking(tracer):
    """Per-bucket-shape compile-cache hit/miss counters: the first
    dispatch of a shape is a miss (an XLA compile), repeats are hits —
    the recompile-storm signal."""
    from gethsharding_tpu.metrics import DEFAULT_REGISTRY
    from gethsharding_tpu.sigbackend import JaxSigBackend

    backend = JaxSigBackend.__new__(JaxSigBackend)  # tracking state only:
    # full __init__ imports + jits the kernels, which the slow tier owns
    backend._shape_seen = set()
    backend._shape_lock = threading.Lock()
    from gethsharding_tpu import metrics as m

    backend._m_shape_hit = m.counter("jax/compile_cache/hits")
    backend._m_shape_miss = m.counter("jax/compile_cache/misses")
    hits0 = backend._m_shape_hit.value
    misses0 = backend._m_shape_miss.value
    assert backend._note_shape("ecrecover", 16) is True     # fresh shape
    assert backend._note_shape("ecrecover", 16) is False    # compiled
    assert backend._note_shape("ecrecover", 32) is True     # new bucket
    assert backend._note_shape("bls_committee", 16, 144) is True
    assert backend._m_shape_miss.value - misses0 == 3
    assert backend._m_shape_hit.value - hits0 == 1
    assert DEFAULT_REGISTRY.get("jax/compile_cache/misses") is not None
