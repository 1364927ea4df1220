"""RPCServer: expose a SimulatedMainchain over JSON-RPC 2.0.

Parity: `rpc/server.go:46` + the IPC codec (`rpc/ipc.go`,
`rpc/json.go`) — newline-delimited JSON-RPC 2.0 frames over a stream
socket, one goroutine-equivalent thread per connection, `shard_subscribe`
push notifications for new heads (the `eth_subscribe` pattern the notary's
head loop depends on, `sharding/notary/notary.go:33-38`).

SMC reverts map to JSON-RPC error code 3 (geth's revert error code) with
the revert reason in `message`; the client re-raises them as `SMCRevert`
so actor-side control flow is identical in- and cross-process.
"""

from __future__ import annotations

import json
import logging
import os
import queue
import socket
import socketserver
import threading
import time
from typing import Optional

from gethsharding_tpu import metrics, tracing
from gethsharding_tpu.rpc import codec
from gethsharding_tpu.p2p.service import (
    PROTOCOL_NAME as P2P_PROTOCOL_NAME,
    PROTOCOL_VERSION as P2P_PROTOCOL_VERSION,
)
from gethsharding_tpu.smc.chain import SimulatedMainchain
from gethsharding_tpu.smc.state_machine import SMCRevert
from gethsharding_tpu.utils.hexbytes import Address20, Hash32

log = logging.getLogger("rpc.server")

REVERT_CODE = 3
METHOD_NOT_FOUND = -32601
INVALID_REQUEST = -32600
INTERNAL_ERROR = -32603

# per-connection dispatch concurrency: one socket carries MANY
# multiplexed requests (a fleet frontend funnels every routed call for
# a replica over ONE RPCClient), so handling them serially in the read
# loop would cap a replica at one in-flight request per upstream and
# starve the serving tier's coalescing + queue-depth signal. Each
# request dispatches on its own worker; the bound makes the read loop
# itself the backpressure once a connection has this many in flight.
CONN_CONCURRENCY = int(os.environ.get(
    "GETHSHARDING_RPC_CONN_CONCURRENCY", "64"))


class _Marks:
    """One request's clock readings on its way through the server: the
    frame's first bytes in the buffer, the request line read off the
    socket (`frame_bytes` long), the JSON parse, the handler's return.
    `stem` is the method without ``shard_`` once a handler was
    found for it (None for a bad frame, an unknown method, the built-in
    subscribe / p2p methods and the trace plane: none of those is
    booked). For a traced request, `span_id` is the id its
    ``rpc/<m>/server_time`` span will get, taken before the handler
    span that names it as parent; `trace_id` / `parent_id` place that
    span in the caller's trace (or make it the root of its own)."""

    __slots__ = ("t_first", "t_read", "frame_bytes", "t_parse", "t_parsed",
                 "t_handled", "stem", "trace_id", "span_id", "parent_id")

    def __init__(self, t_first: float, t_read: float, frame_bytes: int):
        self.t_first = t_first
        self.t_read = t_read
        self.frame_bytes = frame_bytes
        self.t_parse = self.t_parsed = self.t_handled = t_read
        self.stem: Optional[str] = None
        self.trace_id: Optional[int] = None
        self.span_id: Optional[int] = None
        self.parent_id: Optional[int] = None


def _decode_stage(stem: str):
    """The stage a verification-plane handler decodes its arguments in:
    ``rpc/<m>/decode_time``."""
    return tracing.stage(f"rpc/{stem}/decode_time",
                         metrics.timer(f"rpc/{stem}/decode_time"))


# rows of shard_verifyCommittees requests whose signature AND key row
# arrived packed (codec: one string a row), the wire form the limb
# marshal takes without opening a point
_PACKED_ROWS = metrics.counter("rpc/verifyCommittees/packed_rows")


class RPCServer:
    """Threaded JSON-RPC server over TCP (host, port) — port 0 picks a
    free one (`server.address` reports the bound endpoint)."""

    def __init__(self, backend: SimulatedMainchain,
                 host: str = "127.0.0.1", port: int = 0,
                 sig_backend=None, das=None):
        self.backend = backend
        # data-availability sampling provider (a das.service.DASService,
        # or anything with get_sample/da_status): backs the light-client
        # sample surface `shard_getSample` / `shard_daStatus`. None =
        # this process holds no blobs; the methods answer "unknown".
        self._das = das
        self._subscribers: dict = {}  # wfile -> (lock, peer id)
        self._sub_lock = threading.Lock()
        # verification serving seam: handler threads SUBMIT signature
        # work to the coalescing tier instead of driving a backend
        # inline, so concurrent RPC clients share device dispatches
        # (gethsharding_tpu/serving/). Built lazily on first use when
        # not injected — chain processes that never verify pay nothing.
        self._sig_backend = sig_backend
        self._sig_serving = None
        self._sig_serving_owned = False
        # fleet drain lifecycle: a DRAINING server refuses NEW
        # verification work with a typed "replica draining" error (the
        # router retries on the next replica) while in-flight requests
        # finish; `shard_health` exports the flag plus the breaker /
        # serving state the router's health sweep reads
        self.draining = False
        self._inflight = 0
        server = self

        class Handler(socketserver.StreamRequestHandler):
            def handle(self) -> None:
                server._handle_connection(self)

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._tcp = Server((host, port), Handler)
        self.address = self._tcp.server_address  # (host, bound_port)
        self._thread: Optional[threading.Thread] = None
        self._unsubscribe = backend.subscribe_new_head(self._on_head)
        # shardp2p relay: peer id -> (wfile, write lock); actors in other
        # processes attach here for introduction (authenticated peer
        # table + broadcast); directed payloads flow peer-to-peer over
        # the listeners the peers advertise (p2p/direct.py)
        self._p2p_peers: dict = {}
        self._p2p_meta: dict = {}
        self._p2p_ids = 1
        self._p2p_challenges: dict = {}  # wfile -> pending nonce
        self.p2p_relayed_sends = 0  # directed sends that fell back to us
        self.method_calls: dict = {}  # per-method request counts

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._tcp.serve_forever, daemon=True, name="rpc-server")
        self._thread.start()
        log.info("RPC listening on %s:%d", *self.address)

    def stop(self, grace_s: float = 5.0) -> None:
        """Graceful shutdown: stop admitting NEW verification work,
        give in-flight RPC requests a bounded grace to finish, then
        close the serving tier — whose `PipelinedDispatcher.close(
        wait=True)` semantics drain what it can and FAIL the rest with
        `DispatcherClosed`, so a router-initiated drain never strands a
        caller on a future nothing will resolve."""
        self.draining = True
        deadline = time.monotonic() + grace_s
        while self._inflight > 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        if self._unsubscribe is not None:
            self._unsubscribe()
        self._tcp.shutdown()
        self._tcp.server_close()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        # detach under the same lock `_serving()` builds under — a
        # handler still lazily building the tier must never race the
        # teardown's write; close() runs outside the lock (it joins
        # serving threads and must not hold the server's lock doing it)
        with self._sub_lock:
            serving = self._sig_serving if self._sig_serving_owned else None
            if serving is not None:
                self._sig_serving = None
        if serving is not None:
            serving.close()

    def drain(self) -> dict:
        """Router/operator-initiated drain: refuse new verification
        work, drain-and-fail the serving queues (in-flight batches get
        their grace, queued futures fail with `DispatcherClosed` /
        `QueueClosed` instead of hanging). The RPC control surface
        (`shard_drain`) calls this; `stop()` completes the shutdown."""
        self.draining = True
        with self._sub_lock:
            serving = self._sig_serving
        if serving is not None and hasattr(serving, "close") \
                and self._sig_serving_owned:
            serving.close()
        return {"draining": True, "inflight": self._inflight}

    # -- head push (eth_subscribe newHeads parity) -------------------------

    def _on_head(self, block) -> None:
        note = (json.dumps({
            "jsonrpc": "2.0",
            "method": "shard_subscription",
            "params": {"subscription": "newHeads",
                       "result": codec.enc_block(block)},
        }) + "\n").encode()
        with self._sub_lock:
            targets = list(self._subscribers.items())
        for wfile, (lock, peer) in targets:
            try:
                with lock:
                    wfile.write(note)
                    wfile.flush()
            except (OSError, ValueError) as exc:
                # connection-level failures only: the peer reset/broke
                # the pipe (OSError) or the handler already closed its
                # wfile (ValueError). Anything else is a server bug and
                # must surface to the head-feed caller, not silently
                # unsubscribe a healthy peer.
                with self._sub_lock:
                    self._subscribers.pop(wfile, None)
                log.warning("dropping head subscriber %s: %s", peer, exc)

    # -- connection loop ---------------------------------------------------

    def _handle_connection(self, handler) -> None:
        write_lock = threading.Lock()
        slots = threading.BoundedSemaphore(max(1, CONN_CONCURRENCY))
        # The connection's workers outlive their requests: a request
        # runs on an idle worker where there is one and on a new thread
        # only when all are busy. A thread a request was measured at
        # 230 ms of `json.loads` on a 14 MB `shard_dasVerify` frame
        # where the main thread takes 18 (PERF.md section 6, PR 35): a
        # new thread is handed another malloc arena, in turn, once the
        # process has many threads, and the frame's 1,600 strings of 8
        # KB grow that arena page by page every time. A thread that
        # stays keeps the arena it has grown.
        todo: "queue.SimpleQueue" = queue.SimpleQueue()
        workers = []
        idle_lock = threading.Lock()
        idle = 0    # workers waiting for a request, under idle_lock

        def serve_one(raw: bytes, t_first: float, t_read: float) -> None:
            nonlocal idle
            marks = _Marks(t_first, t_read, len(raw))
            try:
                try:
                    response = self._dispatch(raw, handler, write_lock,
                                              marks)
                finally:
                    with self._sub_lock:
                        self._inflight -= 1
                    # idle from here, before the response goes out: a
                    # caller that answers its answer at once must find
                    # this worker, not none
                    with idle_lock:
                        idle += 1
                if response is not None:
                    with write_lock:
                        handler.wfile.write(
                            (json.dumps(response) + "\n").encode())
                        handler.wfile.flush()
            except (OSError, ValueError):
                pass  # peer gone mid-response: its client already knows
            finally:
                slots.release()
                self._book(marks, time.monotonic())

        def work() -> None:
            for raw, t_first, t_read in iter(todo.get, None):
                serve_one(raw, t_first, t_read)
                raw = None  # a waiting worker pins no frame

        rfile = handler.rfile
        try:
            # ``rpc/<m>/recv_time`` (`_book`): block for the frame's
            # FIRST bytes, stamp, then read the line. The peek is the
            # read `readline` would have made, no further system call;
            # a pipelined frame is in the buffer already and reads
            # about 0. Annotated under a fixed name, as the parse is:
            # the method is inside the frame.
            while rfile.peek(1):
                t_first = time.monotonic()
                with tracing.annotation("rpc/recv_time"):
                    raw = rfile.readline()
                t_read = time.monotonic()
                # json.loads takes the line's whitespace; no copy of a
                # frame of megabytes to strip it
                if raw.isspace():
                    continue
                with self._sub_lock:
                    self._inflight += 1
                # concurrent dispatch, bounded: responses multiplex back
                # by request id (the client's pending map reorders), and
                # once CONN_CONCURRENCY requests are in flight the read
                # loop blocks here — TCP backpressure to the sender
                slots.acquire()
                with idle_lock:
                    spawn = idle == 0
                    idle -= not spawn
                if spawn:
                    worker = threading.Thread(target=work, daemon=True,
                                              name="rpc-conn-worker")
                    workers.append(worker)
                    worker.start()
                todo.put((raw, t_first, t_read))
        except (OSError, ValueError):
            pass
        finally:
            # drain in-flight workers briefly (shared deadline, not
            # per-thread): their responses are undeliverable now, and
            # they are daemons — this just keeps teardown orderly
            for _ in workers:
                todo.put(None)
            deadline = time.monotonic() + 1.0
            for worker in workers:
                worker.join(timeout=max(0.0, deadline - time.monotonic()))
            with self._sub_lock:
                self._subscribers.pop(handler.wfile, None)
                self._p2p_challenges.pop(handler.wfile, None)
                dead = [pid for pid, (wf, _) in self._p2p_peers.items()
                        if wf is handler.wfile]
                for pid in dead:
                    self._p2p_peers.pop(pid, None)
                    self._p2p_meta.pop(pid, None)

    def _book(self, marks: _Marks, t_flushed: float) -> None:
        """Close a request's server-side clocks once its response is
        flushed (or its peer was found gone): ``rpc/<m>/server_time``
        (request line read -> response flushed: slot wait, thread start,
        parse, handler, response) and its part ``rpc/<m>/parse_time``,
        always; BEFORE it and no part of it ``rpc/<m>/recv_time`` (the
        frame's first bytes in the buffer -> its line read: what the
        wire and the buffered reads of a frame of megabytes cost this
        side), with the line's length in the counter
        ``rpc/<m>/frame_bytes``. For a request traced by its caller
        ``recv_time`` is a span under the caller's roundtrip, beside
        ``server_time``; with an untraced caller there is nothing to
        hang it under. For a traced request ``server_time`` is also the span
        that encloses the handler span and the leaves around it:
        ``admit`` (read -> parse), ``parse_time``, ``respond`` (handler
        returned -> flushed). The four children do not overlap, so a
        self-time walk of the trace (fleettrace) adds up, and with an
        untraced caller the enclosing span is the trace's one root."""
        stem = marks.stem
        if stem is None:
            return
        metrics.timer(f"rpc/{stem}/recv_time").observe(
            marks.t_read - marks.t_first)
        metrics.counter(f"rpc/{stem}/frame_bytes").inc(marks.frame_bytes)
        metrics.timer(f"rpc/{stem}/parse_time").observe(
            marks.t_parsed - marks.t_parse)
        if marks.span_id is not None:
            record = tracing.TRACER.record
            if marks.parent_id is not None:
                record(f"rpc/{stem}/recv_time", marks.t_first, marks.t_read,
                       trace_id=marks.trace_id, parent_id=marks.parent_id,
                       tags={"bytes": marks.frame_bytes})
            record(f"rpc/{stem}/server_time", marks.t_read, t_flushed,
                   trace_id=marks.trace_id, parent_id=marks.parent_id,
                   span_id=marks.span_id)
            for name, start, end in (
                    ("admit", marks.t_read, marks.t_parse),
                    ("parse_time", marks.t_parse, marks.t_parsed),
                    ("respond", marks.t_handled, t_flushed)):
                record(f"rpc/{stem}/{name}", start, end,
                       trace_id=marks.trace_id, parent_id=marks.span_id)
        # last: who waits for a request to be booked watches this count
        metrics.timer(f"rpc/{stem}/server_time").observe(
            t_flushed - marks.t_read)

    def _dispatch(self, raw: bytes, handler, write_lock,
                  marks: _Marks) -> Optional[dict]:
        # annotated under a fixed name: the method is inside the frame
        marks.t_parse = time.monotonic()
        try:
            with tracing.annotation("rpc/parse_time"):
                req = json.loads(raw)
        except json.JSONDecodeError:
            return {"jsonrpc": "2.0", "id": None,
                    "error": {"code": INVALID_REQUEST, "message": "bad json"}}
        marks.t_parsed = time.monotonic()
        rid = req.get("id")
        method = req.get("method", "")
        params = req.get("params", [])
        trace_id = None
        handler_span_id = None
        with self._sub_lock:
            self.method_calls[method] = self.method_calls.get(method, 0) + 1
        try:
            if method == "shard_subscribe":
                try:
                    peer = "%s:%d" % handler.client_address[:2]
                except (TypeError, IndexError):
                    peer = repr(handler.client_address)
                with self._sub_lock:
                    self._subscribers[handler.wfile] = (write_lock, peer)
                result = "newHeads"
            elif method == "shard_p2pChallenge":
                import secrets

                nonce = secrets.token_bytes(32)
                with self._sub_lock:
                    self._p2p_challenges[handler.wfile] = nonce
                result = nonce.hex()
            elif method == "shard_p2pAttach":
                handshake = params[0] if params else {}
                self._check_handshake(handshake)
                account = self._check_attach_signature(handshake, handler)
                endpoint = handshake.get("endpoint")
                with self._sub_lock:
                    peer_id = self._p2p_ids
                    self._p2p_ids += 1
                    self._p2p_peers[peer_id] = (handler.wfile, write_lock)
                    self._p2p_meta[peer_id] = {
                        "account": account,
                        "endpoint": (None if endpoint is None
                                     else list(endpoint)),
                        "version": handshake.get(
                            "version", P2P_PROTOCOL_VERSION),
                    }
                result = peer_id
            else:
                fn = getattr(self, "rpc_" + method.replace("shard_", "", 1),
                             None)
                if fn is None:
                    return {"jsonrpc": "2.0", "id": rid,
                            "error": {"code": METHOD_NOT_FOUND,
                                      "message": f"unknown method {method}"}}
                stem = fn.__name__[len("rpc_"):]
                # per-request handler span: parents any serving-tier
                # request spans the handler submits (the cross-process
                # attribution seam), and its trace id rides back to the
                # client on the response envelope. Extra envelope keys
                # are legal JSON-RPC: clients read `result`/`error` only.
                # An inbound `trace` envelope (RPCClient.call attaches
                # the caller's span context) is ADOPTED: the request
                # joins the remote trace under the remote span,
                # stitching a router-traced request into this replica's
                # spans. Between the two lies ``rpc/<m>/server_time``
                # (`_book`), whose id is taken here.
                if method in codec.TRACE_PLANE_METHODS:
                    # the trace plane is invisible to tracing (see
                    # codec.TRACE_PLANE_METHODS): no handler span, no
                    # trace fields on the response envelope
                    result = fn(*params)
                else:
                    marks.stem = stem
                    ctx = None
                    tracer = tracing.TRACER
                    if tracer.enabled:
                        inbound = req.get("trace")
                        if (isinstance(inbound, dict)
                                and inbound.get("trace_id") is not None):
                            marks.trace_id = int(inbound["trace_id"])
                            parent_id = inbound.get("span_id")
                            marks.parent_id = (None if parent_id is None
                                               else int(parent_id))
                        else:
                            marks.trace_id = tracer.new_trace_id()
                        marks.span_id = tracer.new_trace_id()
                        ctx = (marks.trace_id, marks.span_id)
                    handler_span = tracing.span(f"rpc/{method}", ctx=ctx)
                    try:
                        with handler_span:
                            result = fn(*params)
                    finally:
                        marks.t_handled = time.monotonic()
                    trace_id = handler_span.trace_id
                    handler_span_id = handler_span.span_id
        except SMCRevert as exc:
            return {"jsonrpc": "2.0", "id": rid,
                    "error": {"code": REVERT_CODE, "message": str(exc),
                              "data": "SMCRevert"}}
        except Exception as exc:  # noqa: BLE001 - RPC boundary
            log.exception("rpc %s failed", method)
            return {"jsonrpc": "2.0", "id": rid,
                    "error": {"code": INTERNAL_ERROR, "message": str(exc)}}
        if rid is None:
            return None  # notification
        response = {"jsonrpc": "2.0", "id": rid, "result": result}
        if trace_id is not None:
            response["trace"] = trace_id
            # the full handler context alongside the bare id (kept for
            # older clients): span_id lets the caller and the fleet
            # collector stitch THIS request/response pair exactly —
            # a trace id alone is ambiguous under retries and hedges
            response["traceCtx"] = {"trace_id": trace_id,
                                    "span_id": handler_span_id}
        return response

    # -- method surface (shard_* namespace) --------------------------------
    # views

    def rpc_blockNumber(self):
        return self.backend.block_number

    def rpc_currentPeriod(self):
        return self.backend.current_period()

    def rpc_blockByNumber(self, number=None):
        return codec.enc_block(self.backend.block_by_number(number))

    def rpc_shardCount(self):
        return self.backend.smc.shard_count

    def rpc_getNotaryInCommittee(self, sender, shard_id):
        return codec.enc_bytes(self.backend.get_notary_in_committee(
            Address20(codec.dec_bytes(sender)), shard_id))

    def rpc_committeeContext(self):
        ctx = self.backend.committee_context()
        return {
            "period": ctx["period"],
            "sampleSize": ctx["sample_size"],
            "blockhash": codec.enc_bytes(ctx["blockhash"]),
            "pool": [None if a is None else codec.enc_bytes(a)
                     for a in ctx["pool"]],
        }

    def rpc_notaryRegistry(self, address):
        return codec.enc_registry(self.backend.notary_registry(
            Address20(codec.dec_bytes(address))))

    def rpc_collationRecord(self, shard_id, period):
        return codec.enc_record(self.backend.collation_record(shard_id, period))

    def rpc_lastSubmittedCollation(self, shard_id):
        return self.backend.last_submitted_collation(shard_id)

    def rpc_lastApprovedCollation(self, shard_id):
        return self.backend.last_approved_collation(shard_id)

    def rpc_notaryByPoolIndex(self, index):
        addr = self.backend.notary_by_pool_index(index)
        return None if addr is None else codec.enc_bytes(addr)

    def rpc_hasVoted(self, shard_id, index):
        return self.backend.smc.has_voted(shard_id, index)

    def rpc_getVoteCount(self, shard_id):
        return self.backend.smc.get_vote_count(shard_id)

    def rpc_balanceOf(self, address):
        return self.backend.balance_of(Address20(codec.dec_bytes(address)))

    def rpc_transactionReceipt(self, tx_hash):
        receipt = self.backend.transaction_receipt(
            Hash32(codec.dec_bytes(tx_hash)))
        return None if receipt is None else codec.enc_receipt(receipt)

    def rpc_traceTransaction(self, tx_hash):
        """The debug_traceTransaction role (`eth/api_tracer.go`) for the
        native engine: the SMC's emitted events ARE the execution trace
        (one entry per state-machine effect), returned with the receipt
        frame. None for unknown hashes."""
        receipt = self.backend.transaction_receipt(
            Hash32(codec.dec_bytes(tx_hash)))
        if receipt is None:
            return None
        def enc_arg(value):
            if isinstance(value, (bytes, bytearray)) \
                    or hasattr(value, "__bytes__"):  # Address20 / Hash32
                return codec.enc_bytes(bytes(value))
            return value

        return {
            "txHash": codec.enc_bytes(receipt.tx_hash),
            "status": receipt.status,
            "blockNumber": receipt.block_number,
            "trace": [{"event": e.name,
                       "args": {k: enc_arg(v) for k, v in e.args.items()}}
                      for e in receipt.events],
        }

    def rpc_verifyPeriodBatch(self, period):
        return self.backend.verify_period_batch(period)

    # -- verification serving (the coalescing tier) ------------------------

    def _serving(self):
        """The shared serving backend, built on first use. Injected
        backends that already expose `submit` (a `ServingSigBackend`)
        are used as-is (and not closed by us); a plain `SigBackend`
        gets wrapped."""
        with self._sub_lock:
            if self._sig_serving is None:
                inner = self._sig_backend
                if inner is not None and hasattr(inner, "submit"):
                    self._sig_serving = inner
                else:
                    from gethsharding_tpu.serving import ServingSigBackend
                    from gethsharding_tpu.sigbackend import get_backend

                    self._sig_serving = ServingSigBackend(
                        inner or get_backend("python"))
                    self._sig_serving_owned = True
            return self._sig_serving

    def _check_accepting(self, method: str) -> None:
        if self.draining:
            # the router's retry ladder keys on this phrase: a draining
            # replica is a routing fact, not a caller error
            raise RuntimeError(f"replica draining: {method} refused")

    def rpc_ecrecover(self, digests, sigs, klass=None, tenant=None):
        """Batch address recovery for external clients (txpool feeders,
        light verifiers). The serving backend's sync face enqueues and
        parks the handler thread on the request's future — while this
        batch waits out its flush window, other connection threads
        enqueue into the SAME dispatch, so N concurrent small requests
        cost one device batch instead of N. (The sync face also records
        the future_wake trace phase — one await-then-wake sequence for
        every entry point, serving/backend.py.) The optional trailing
        `klass`/`tenant` params tag the request's admission class and
        quota bucket (serving/classes.py) — a catch-up replayer passes
        ``"catchup_replay"`` and is shed first under overload."""
        self._check_accepting("shard_ecrecover")
        from gethsharding_tpu.serving.classes import admission_class

        serving = self._serving()
        with _decode_stage("ecrecover"):
            digests = [codec.dec_bytes(d) for d in digests]
            sigs = [codec.dec_bytes(s) for s in sigs]
        if klass is not None or tenant is not None:
            # tenant without class still enters the context: the quota
            # must charge the tenant even when the caller says nothing
            # about class (default interactive, this op's default)
            with admission_class(klass or "interactive", tenant):
                out = serving.ecrecover_addresses(digests, sigs)
        else:
            out = serving.ecrecover_addresses(digests, sigs)
        return [None if addr is None else codec.enc_bytes(bytes(addr))
                for addr in out]

    def rpc_verifyAggregates(self, messages, agg_sigs, agg_pks,
                             klass=None, tenant=None):
        """Batch aggregate-vote verification over the serving tier (the
        coalescing analog of the notary's bls_verify_aggregates); the
        optional trailing params tag the admission class like
        shard_ecrecover's."""
        self._check_accepting("shard_verifyAggregates")
        from gethsharding_tpu.serving.classes import admission_class

        serving = self._serving()
        with _decode_stage("verifyAggregates"):
            args = ([codec.dec_bytes(m) for m in messages],
                    [codec.dec_g1(s) for s in agg_sigs],
                    [codec.dec_g2(p) for p in agg_pks])
        if klass is not None or tenant is not None:
            # see shard_ecrecover: a tenant tag alone still charges the
            # quota under this op's default class
            with admission_class(klass or "interactive", tenant):
                out = serving.bls_verify_aggregates(*args)
        else:
            out = serving.bls_verify_aggregates(*args)
        return [bool(b) for b in out]

    def rpc_verifyCommittees(self, messages, sig_rows, pk_rows,
                             pk_row_keys=None, klass=None, tenant=None):
        """The committee plane over the wire: batch aggregate-and-
        verify of per-row vote signatures + member pubkeys through the
        serving tier (the op the notary's period audit drives — with
        this RPC a fleet frontend balances audits cross-process
        instead of pinning them to the caller's device). `pk_row_keys`
        are the optional per-row pk-plane cache keys (wire form:
        codec.enc_pk_row_keys), so a repeat committee stays
        device-resident on the replica exactly as it would in-process.
        The optional trailing `klass`/`tenant` tag admission like
        shard_ecrecover's (a notary's bulk_audit context rides the
        wire as an explicit klass; tenant-only still charges the quota
        under this op's default class)."""
        self._check_accepting("shard_verifyCommittees")
        from gethsharding_tpu.serving.classes import admission_class

        serving = self._serving()
        with _decode_stage("verifyCommittees"):
            *args, keys, packed = codec.dec_committee_call(
                messages, sig_rows, pk_rows, pk_row_keys)
        _PACKED_ROWS.inc(packed)
        if klass is not None or tenant is not None:
            with admission_class(klass or "interactive", tenant):
                out = serving.bls_verify_committees(*args,
                                                    pk_row_keys=keys)
        else:
            out = serving.bls_verify_committees(*args, pk_row_keys=keys)
        return [bool(b) for b in out]

    def rpc_dasVerify(self, chunks, indices, proofs, roots,
                      klass=None, tenant=None):
        """The DAS sample-verdict plane over the wire: one verdict per
        (chunk, index, proof path, root) row through the serving tier
        (serving op `das_verify`, default class bulk_audit via the
        per-op map). Malformed rows cost a False verdict, never an
        error — the same hostile-input contract as the in-process op."""
        self._check_accepting("shard_dasVerify")
        from gethsharding_tpu.serving.classes import admission_class

        serving = self._serving()
        with _decode_stage("dasVerify"):
            args = codec.dec_das_call(chunks, indices, proofs, roots)
        if klass is not None or tenant is not None:
            with admission_class(klass or "bulk_audit", tenant):
                out = serving.das_verify_samples(*args)
        else:
            out = serving.das_verify_samples(*args)
        return [bool(b) for b in out]

    def rpc_dasPolyVerify(self, commitments, index_rows, eval_rows,
                          proofs, ns, klass=None, tenant=None):
        """The DAS multiproof-verdict plane over the wire: one verdict
        per sampled collation row (64-byte poly commitment, sampled
        index set, claimed evaluations, 64-byte multiproof, domain
        size) through the serving tier (serving op `das_poly_verify`,
        default class bulk_audit via the per-op map; light clients
        pass `interactive`). Malformed rows cost a False verdict,
        never an error."""
        self._check_accepting("shard_dasPolyVerify")
        from gethsharding_tpu.serving.classes import admission_class

        serving = self._serving()
        with _decode_stage("dasPolyVerify"):
            args = codec.dec_das_poly_call(commitments, index_rows,
                                           eval_rows, proofs, ns)
        if klass is not None or tenant is not None:
            with admission_class(klass or "bulk_audit", tenant):
                out = serving.das_verify_multiproofs(*args)
        else:
            out = serving.das_verify_multiproofs(*args)
        return [bool(b) for b in out]

    def rpc_health(self):
        """The replica-health surface a fleet router sweeps: the drain
        flag, the failover breaker's state (if the injected backend
        composes one), the device record of the backend that answers
        (platform / device_kind / count; None for a scalar backend),
        and the serving tier's per-class queue depths. One round trip,
        cheap enough for sub-second polling."""
        from gethsharding_tpu.fleet.router import breaker_of
        from gethsharding_tpu.sigbackend import device_record_of

        payload = {"draining": self.draining,
                   # minus one: this health request is itself in flight
                   "inflight": max(0, self._inflight - 1),
                   "breaker": None, "serving": None,
                   "device": device_record_of(self._sig_backend)}
        backend = self._sig_backend
        if backend is not None:
            breaker = breaker_of(backend)
            if breaker is not None:
                payload["breaker"] = breaker.state_name
        with self._sub_lock:
            serving = self._sig_serving
        batcher = getattr(serving, "batcher", None)
        if batcher is None:
            # the serving tier may hide under a failover/soundness face
            probe, hops = serving, 0
            while probe is not None and hops < 8 and batcher is None:
                batcher = getattr(probe, "batcher", None)
                probe, hops = getattr(probe, "inner", None), hops + 1
        if batcher is not None:
            payload["serving"] = {
                "shed": batcher.shed_by_class(),
                "quota_rejections": batcher.quota_rejections(),
                "depth": {op: batcher.class_depths(op)
                          for op in batcher.dispatch_counts},
            }
        return payload

    def rpc_drain(self):
        """Router/operator-initiated drain (see `drain()`)."""
        return self.drain()

    def rpc_metrics(self):
        """Metrics federation: this replica's full registry snapshot in
        ONE round trip — the scrape the fleet router's background
        health sweep folds into its own registry under
        ``fleet/replica/<name>/...`` (plus fleet-level aggregates), so
        a router's /status answers "which replica's chip is slow"
        without dialing N dashboards. Snapshots are plain JSON-safe
        dicts (counters/gauges/timers/histograms)."""
        from gethsharding_tpu.metrics import DEFAULT_REGISTRY

        return DEFAULT_REGISTRY.snapshot()

    # -- fleet tracing (the fleettrace control surface) --------------------

    def rpc_traceHandshake(self):
        """Clock-offset handshake: the exporter reads this process's
        wall clock mid-round-trip (NTP midpoint estimate) to measure
        the per-connection skew it stamps on every span batch — the
        cross-HOST extension of the `clock_offset_us` anchor."""
        import os

        from gethsharding_tpu.tracing.export import clock_offset_us

        return {"wall_us": time.time() * 1e6,
                "clock_offset_us": clock_offset_us(),
                "pid": os.getpid()}

    def rpc_traceExport(self, payload):
        """Span-batch sink: accept one exporter batch into this
        process's fleettrace collector (``accepted: false`` when no
        collector is booted — a replica is a producer, not an owner)."""
        from gethsharding_tpu import fleettrace

        collector = fleettrace.active()
        if collector is None:
            return {"accepted": False, "spans": 0}
        return collector.ingest_payload(payload)

    def rpc_traceAttribution(self):
        """Per-class critical-path attribution tables (None when no
        collector is booted)."""
        from gethsharding_tpu import fleettrace

        collector = fleettrace.active()
        return None if collector is None else collector.attribution()

    def rpc_traceExemplars(self, limit=8):
        """Most recent retained (tail-sampled) assembled traces,
        newest first — full span trees, the post-mortem payload."""
        from gethsharding_tpu import fleettrace

        collector = fleettrace.active()
        return [] if collector is None else collector.exemplars(
            limit=int(limit))

    # -- on-demand profiling (the devscope control surface) ----------------

    def rpc_profileStart(self, mode=None, hz=None):
        """Begin an on-demand profiling session on THIS process:
        ``mode`` = ``sampler`` (pure-Python collapsed-stack sampler),
        ``jax`` (a jax.profiler trace into the bounded devscope
        profile directory), or ``both`` (the default). Idempotent — a
        session already running is reported, never doubled. The
        StatusServer's ``/profile?action=start`` drives the same
        manager."""
        from gethsharding_tpu.devscope import PROFILER

        return PROFILER.start(mode=mode,
                              hz=None if hz is None else float(hz))

    def rpc_profileStop(self):
        """End the profiling session (no-op when none is running);
        returns the session summary incl. the jax trace directory and
        the sampler's sample counts."""
        from gethsharding_tpu.devscope import PROFILER

        return PROFILER.stop()

    def rpc_profileStacks(self):
        """The sampler's collapsed-stack text (running session, or the
        last finished one) — the RPC twin of ``/profile/stacks`` for
        processes that serve no StatusServer (chain_server replicas)."""
        from gethsharding_tpu.devscope import PROFILER

        return PROFILER.stacks()

    def rpc_devscopeStatus(self):
        """The device-introspection snapshot (memory poller, compile
        watch, profiler) — what a node's /status ``devscope`` section
        shows, for RPC-only processes."""
        from gethsharding_tpu.devscope import devscope_status

        return devscope_status()

    def rpc_servingStats(self):
        """Dispatch/coalescing counters of the serving tier (None until
        the first submit builds it)."""
        with self._sub_lock:
            serving = self._sig_serving
        if serving is None or not hasattr(serving, "batcher"):
            return None
        return {"dispatches": dict(serving.batcher.dispatch_counts),
                "shed": serving.batcher.shed_counts()}

    # -- data-availability sampling (the light-client sample surface) ------

    def rpc_getSample(self, shard_id, period, indices):
        """Sampled chunks + inclusion proofs for (shard, period) from
        this process's DAS provider — the RPC (light-client) face of
        the shardp2p DASampleRequest flow: a client that can reach no
        sampling peers still gets proof-carrying samples it verifies
        locally against the returned commitment. None when no provider
        holds the blob."""
        if self._das is None:
            return None
        from gethsharding_tpu.das.service import MAX_SAMPLE_INDICES

        status = self._das.da_status(int(shard_id), int(period))
        if not status.get("known"):
            return None
        samples = []
        # same per-request cap as the p2p serving side
        for index in list(indices)[:MAX_SAMPLE_INDICES]:
            sample = self._das.get_sample(int(shard_id), int(period),
                                          int(index))
            if sample is None:
                continue
            samples.append({
                "index": sample["index"],
                "chunk": codec.enc_bytes(sample["chunk"]),
                "proof": [codec.enc_bytes(node)
                          for node in sample["proof"]],
            })
        commitment = self._das.commitment(int(shard_id), int(period))
        out = {
            "dasRoot": codec.enc_bytes(commitment.das_root),
            "chunkRoot": codec.enc_bytes(commitment.chunk_root),
            "k": commitment.k,
            "n": commitment.n,
            "bodyLen": commitment.body_len,
            "signature": codec.enc_bytes(commitment.signature),
            "samples": samples,
        }
        # poly plane: under --da-proofs=poly the k merkle paths above
        # collapse to ONE constant-size multiproof over the whole set
        # (das/pcs.py) — the client verifies it against polyCommitment
        poly = bytes(getattr(commitment, "poly_commitment", b""))
        if poly:
            out["polyCommitment"] = codec.enc_bytes(poly)
        if getattr(self._das, "proof_mode", "merkle") == "poly":
            multi = self._das.get_multiproof(
                int(shard_id), int(period),
                [int(i) for i in list(indices)[:MAX_SAMPLE_INDICES]])
            if multi is not None:
                out["multiproof"] = {
                    "indices": list(multi["indices"]),
                    "chunks": [codec.enc_bytes(c)
                               for c in multi["chunks"]],
                    "proof": codec.enc_bytes(multi["proof"]),
                }
        return out

    def rpc_daStatus(self, shard_id, period):
        """Is a DAS commitment known for (shard, period), and what
        shape is the erasure extension? `known: false` with
        `provider: false` means this process runs no DAS plane at
        all."""
        if self._das is None:
            return {"known": False, "provider": False,
                    "shard_id": int(shard_id), "period": int(period)}
        status = self._das.da_status(int(shard_id), int(period))
        status["provider"] = True
        return status

    # transactions

    def rpc_registerNotary(self, sender, bls_pubkey=None, bls_pop=None):
        return codec.enc_receipt(self.backend.register_notary(
            Address20(codec.dec_bytes(sender)),
            bls_pubkey=codec.dec_g2(bls_pubkey),
            bls_pop=codec.dec_g1(bls_pop)))

    def rpc_deregisterNotary(self, sender):
        return codec.enc_receipt(self.backend.deregister_notary(
            Address20(codec.dec_bytes(sender))))

    def rpc_releaseNotary(self, sender):
        return codec.enc_receipt(self.backend.release_notary(
            Address20(codec.dec_bytes(sender))))

    def rpc_addHeader(self, sender, shard_id, period, chunk_root, signature):
        return codec.enc_receipt(self.backend.add_header(
            Address20(codec.dec_bytes(sender)), shard_id, period,
            Hash32(codec.dec_bytes(chunk_root)),
            codec.dec_bytes(signature)))

    def rpc_submitVote(self, sender, shard_id, period, index, chunk_root,
                       bls_sig=None):
        return codec.enc_receipt(self.backend.submit_vote(
            Address20(codec.dec_bytes(sender)), shard_id, period, index,
            Hash32(codec.dec_bytes(chunk_root)),
            bls_sig=codec.dec_g1(bls_sig)))

    # dev-mode chain control (the SimulatedBackend Commit/FastForward
    # surface, exposed so a test/driver process can steer the chain)

    # shardp2p relay (the cross-process feed-bus transport; see
    # gethsharding_tpu/p2p/remote.py)

    def _p2p_push(self, peer_id, note_bytes) -> bool:
        with self._sub_lock:
            entry = self._p2p_peers.get(peer_id)
        if entry is None:
            return False
        wfile, lock = entry
        try:
            with lock:
                wfile.write(note_bytes)
                wfile.flush()
            return True
        except OSError:
            with self._sub_lock:
                self._p2p_peers.pop(peer_id, None)
            return False

    @staticmethod
    def _p2p_note(to_id, from_id, kind, payload) -> bytes:
        return (json.dumps({
            "jsonrpc": "2.0", "method": "shard_p2p",
            "params": {"to": to_id, "from": from_id, "type": kind,
                       "payload": payload},
        }) + "\n").encode()

    def _check_handshake(self, handshake: dict) -> None:
        """Protocol/version/network gate (p2p/protocol.go + the eth status
        exchange, scoped to the relay's trust model). Absent fields pass —
        an attacher that states nothing claims nothing — but any STATED
        field must match."""
        proto = handshake.get("protocol", P2P_PROTOCOL_NAME)
        if proto != P2P_PROTOCOL_NAME:
            raise ValueError(f"protocol mismatch: {proto!r}")
        version = handshake.get("version", P2P_PROTOCOL_VERSION)
        if version != P2P_PROTOCOL_VERSION:
            raise ValueError(
                f"version mismatch: peer {version}, ours {P2P_PROTOCOL_VERSION}")
        network = handshake.get("network_id")
        ours = self.backend.config.network_id
        if network is not None and network != ours:
            raise ValueError(f"network mismatch: peer {network}, ours {ours}")

    def _check_attach_signature(self, handshake: dict, handler) -> str:
        """Authenticated attach: the claimed account must be PROVEN by a
        secp256k1 signature over a challenge this relay issued on this
        connection. Unsigned or forged attaches are refused — the
        reference's RLPx authenticates both ends cryptographically
        (p2p/rlpx.go:178); a self-claimed identity would let any process
        impersonate a notary on the data-availability plane."""
        from gethsharding_tpu.p2p import direct

        account = handshake.get("account")
        sig_hex = handshake.get("sig")
        if not account or not sig_hex:
            raise ValueError(
                "unsigned attach refused: account + sig required")
        with self._sub_lock:
            challenge = self._p2p_challenges.pop(handler.wfile, None)
        if challenge is None:
            raise ValueError(
                "no pending challenge: call shard_p2pChallenge first")
        digest = direct.attach_digest(self.backend.config.network_id,
                                      challenge)
        if not direct.prove(digest, bytes.fromhex(sig_hex), account):
            raise ValueError(
                "attach signature does not prove the claimed account")
        return account.lower().removeprefix("0x")

    def rpc_p2pPeers(self):
        """Attached-peer table (admin_peers parity for the relay)."""
        with self._sub_lock:
            return [{"id": pid, **self._p2p_meta.get(pid, {})}
                    for pid in sorted(self._p2p_peers)]

    def rpc_networkId(self):
        return self.backend.config.network_id

    def rpc_auditData(self, period):
        """Bulk period-audit pull (records + vote sigs + voter pubkeys):
        ONE round trip for what would be O(shards) record reads plus
        O(votes) registry lookups (mainchain/mirror.assemble_audit_data)."""
        from gethsharding_tpu.mainchain.mirror import assemble_audit_data

        return assemble_audit_data(self.backend, period)

    def rpc_mirrorSnapshot(self):
        """Bulk state-mirror pull: ONE round trip for what would be
        ~3 calls per shard (mainchain/mirror.py)."""
        from gethsharding_tpu.mainchain.mirror import assemble_snapshot

        return assemble_snapshot(self.backend)

    def rpc_chainConfig(self):
        """The chain process's protocol constants — attached actors adopt
        these instead of trusting their own flags (one source of truth
        for period/committee math across processes)."""
        import dataclasses

        return dataclasses.asdict(self.backend.config)

    def rpc_p2pDetach(self, peer_id):
        with self._sub_lock:
            self._p2p_peers.pop(peer_id, None)
            self._p2p_meta.pop(peer_id, None)
        return True

    def rpc_p2pPeerInfo(self, peer_id):
        """Introduction lookup: the proven account + direct-listener
        endpoint for one peer (None if unknown)."""
        with self._sub_lock:
            meta = self._p2p_meta.get(peer_id)
        return None if meta is None else dict(meta)

    def rpc_p2pStats(self):
        return {"relayed_sends": self.p2p_relayed_sends,
                "peers": len(self._p2p_peers)}

    def rpc_methodStats(self):
        """Per-method request counts (chatter observability: the mirror's
        O(1)-per-head contract is asserted against these)."""
        with self._sub_lock:
            return dict(self.method_calls)

    def rpc_p2pSend(self, from_id, to_id, kind, payload):
        # handler threads are concurrent: the relayed-sends count is a
        # read-modify-write and takes the same lock as the peer tables
        with self._sub_lock:
            self.p2p_relayed_sends += 1
        return self._p2p_push(to_id,
                              self._p2p_note(to_id, from_id, kind, payload))

    def rpc_p2pBroadcast(self, from_id, kind, payload):
        with self._sub_lock:
            targets = [pid for pid in self._p2p_peers if pid != from_id]
        delivered = 0
        for pid in targets:
            if self._p2p_push(pid, self._p2p_note(pid, from_id, kind,
                                                  payload)):
                delivered += 1
        return delivered

    def rpc_fund(self, address, amount):
        self.backend.fund(Address20(codec.dec_bytes(address)), amount)
        return True

    def rpc_commit(self):
        return codec.enc_block(self.backend.commit())

    def rpc_fastForward(self, periods):
        self.backend.fast_forward(periods)
        return self.backend.block_number

    def rpc_setHead(self, number):
        """Dev-mode rollback (debug_setHead parity)."""
        return codec.enc_block(self.backend.set_head(number))

    def rpc_blockRange(self, start, end):
        """Blocks [start, end] inclusive — the header-download surface a
        follower chain process syncs from (eth/downloader role)."""
        start, end = int(start), int(end)
        if start < 0 or end > self.backend.block_number or end - start > 4096:
            raise ValueError("bad block range")
        return [codec.enc_block(self.backend.block_by_number(n))
                for n in range(start, end + 1)]

    def rpc_stateCheckpoint(self):
        """Full-state checkpoint at the current head (the fast-sync
        pivot-state analog) for follower chain processes."""
        return self.backend.state_checkpoint()

    def rpc_stateSeq(self):
        """Cheap state identity for followers' steady-state polling."""
        return self.backend.state_seq()
