"""Stage clocks (ISSUE 26): one `shard_verifyCommittees` request (and,
since ISSUE 35, one `shard_dasVerify` request: the same tests, a case
each) through an in-process RPC server over the jax backend, at a tiny
shape on the CPU, read three ways: the registry timers (always on), the tracer's
spans (one trace id from the client's call to the device pull), and the
benchmark's per-layer metric files over two `shard_metrics` snapshots.
Plus the primitive alone, the collector's clock and the kernels' names.
Since ISSUE 36 the client sends a committee row packed, one string a row;
the coordinate lists of an older client are a case of the same tests.
Since ISSUE 37 the caller's half of the request has stage clocks too
(`rpc/client/<m>/...`, read under `caller/` in `RpcReplicaBackend.metrics`),
the server times the frame's receive, and the three device operations no
cell drives (`shard_ecrecover`, `shard_verifyAggregates`,
`shard_dasPolyVerify`) are cases of the registry's tests. Since ISSUE 39
the multiproof's coefficients have a stage of their own inside the host
marshal, and a counter holds the rows whose MSMs the device summed.

Counts and containment only: no time measured here means anything.
"""

import gc
import json
import os
import socket
import sys
import time

import pytest

from gethsharding_tpu import metrics, tracing
from gethsharding_tpu.crypto import bn256 as bls

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OP = "bls_committee"
RPC = "rpc/verifyCommittees/"
# the RPC methods whose request enters every stage, each with its serving
# op, the span of its dispatch and the fixtures that hold one request of it
METHODS = {
    "verifyCommittees": {"op": OP, "dispatch": "jax/bls_committee_dispatch",
                         "untraced": "untraced", "traced": "traced"},
    "dasVerify": {"op": "das_verify", "dispatch": "jax/das_verify_dispatch",
                  "untraced": "das_untraced", "traced": "das_traced"},
}
# the device operations no cell drives: the same stages since ISSUE 37,
# each read over one untraced request (the fixture `<method>_untraced`)
PLAIN = {"ecrecover": "ecrecover", "verifyAggregates": "bls_aggregate",
         "dasPolyVerify": "das_poly_verify"}
# the caller's stage clocks of one call, as `RpcReplicaBackend.metrics`
# lays them beside the replica's rows; `{m}` is the method
CLIENT_TIMERS = tuple("caller/rpc/client/{m}/" + leaf for leaf in (
    "encode_time", "dumps_time", "send_time", "wait_time", "reply_time"))
# what lies between the caller's first line and its wait's end, in turn
CLIENT_PARTS = CLIENT_TIMERS[:4]
SIG_SPANS = ("sig/host_marshal_time", "sig/transfer_time",
             "sig/launch_time", "sig/block_time", "sig/pull_time")
# `{rpc}` is the method's `rpc/<m>/`, `{op}` its serving op
STAGE_TIMERS = ("{rpc}recv_time", "{rpc}server_time", "{rpc}parse_time",
                "{rpc}decode_time", *SIG_SPANS)
# each whole covers its parts
WHOLES = {
    "sig/marshal_time": ("sig/host_marshal_time", "sig/transfer_time"),
    "sig/device_time": ("sig/launch_time", "sig/block_time",
                        "sig/pull_time"),
    "{rpc}server_time": ("{rpc}parse_time", "{rpc}decode_time",
                         "serving/{op}/wait_time",
                         "serving/{op}/dispatch_latency"),
}


def _named(name, method):
    op = PLAIN.get(method) or METHODS[method]["op"]
    return name.format(rpc=f"rpc/{method}/", op=op, m=method)


def _committees():
    """Two rows x two votes, one row with a vote on another message."""
    keys = [bls.bls_keygen(b"stage-clock-%d" % i) for i in range(2)]
    msgs = [b"stage-header-0", b"stage-header-1"]
    sig_rows = [[bls.bls_sign(m, sk) for sk, _ in keys] for m in msgs]
    sig_rows[1][0] = bls.bls_sign(b"another header", keys[0][0])
    pk_rows = [[pk for _, pk in keys] for _ in msgs]
    return msgs, sig_rows, pk_rows, [True, False]


# a line-table miss alone enters these two, inside sig/transfer_time
LINE_STAGES = ("sig/line_precompute_time", "sig/line_stack_time")


def _keyed_committees():
    """Four rows x three votes, all good: bucket 4 and width 4, the
    shapes `tests/test_sigbackend_precomp.py` keeps warm."""
    keys = [bls.bls_keygen(b"stage-clock-keyed-%d" % i) for i in range(3)]
    msgs = [b"stage-keyed-header-%d" % i for i in range(4)]
    sig_rows = [[bls.bls_sign(m, sk) for sk, _ in keys] for m in msgs]
    return msgs, sig_rows, [[pk for _, pk in keys] for _ in msgs], [True] * 4


def _das_samples():
    """Four sampled rows of one three-chunk body, one of them withheld:
    bucket 4, the shape `tests/test_das_period.py` keeps warm."""
    from gethsharding_tpu.das import erasure, proofs

    body = erasure.extend_body(bytes(range(256)) * 32, parity_ratio=0.5)
    levels = proofs.merkle_levels([proofs.chunk_leaf(c)
                                   for c in body.chunks])
    indices = [0, 1, 2, 1]
    chunks = [body.chunks[i] for i in indices]
    chunks[3] = bytes(reversed(chunks[3]))
    return (chunks, indices, [proofs.merkle_proof(levels, i)
                              for i in indices],
            [levels[-1][0]] * 4, [True, True, True, False])


def _plain_calls():
    """One row each of the three operations no cell drives: (the
    client's method, its arguments, the verdicts wanted) by RPC method."""
    from gethsharding_tpu.crypto import secp256k1 as ecdsa
    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.das import pcs
    from gethsharding_tpu.das.pcs import commit, g1_to_bytes, open_multi

    priv = int.from_bytes(keccak256(b"stage-clock-ecdsa"), "big") % ecdsa.N
    digest = keccak256(b"stage-clock-digest")
    sk, pk = bls.bls_keygen(b"stage-clock-aggregate")
    values = [(7 * i + 3) % pcs.N for i in range(4)]
    proof, evals = open_multi(values, (1, 2))
    return {
        "ecrecover": ("ecrecover_addresses",
                      ([digest], [ecdsa.sign(digest, priv).to_bytes65()]),
                      [ecdsa.priv_to_address(priv)]),
        "verifyAggregates": ("bls_verify_aggregates",
                             ([b"stage-header"],
                              [bls.bls_sign(b"stage-header", sk)], [pk]),
                             [True]),
        "dasPolyVerify": ("das_verify_multiproofs",
                          ([g1_to_bytes(commit(values))], [[1, 2]], [evals],
                           [g1_to_bytes(proof)], [4]), [True]),
    }


class Served:
    """The in-process server, its client and one request's arguments."""

    def __init__(self):
        from gethsharding_tpu.fleet.router import RpcReplicaBackend
        from gethsharding_tpu.rpc.server import RPCServer
        from gethsharding_tpu.serving import ServingSigBackend
        from gethsharding_tpu.sigbackend import JaxSigBackend
        from gethsharding_tpu.smc.chain import SimulatedMainchain

        *self.args, self.want = _committees()
        self.keyed, self.keyed_sent = _keyed_committees(), 0
        self.das = _das_samples()
        self.plain = _plain_calls()
        self.serving = ServingSigBackend(JaxSigBackend())
        self.server = RPCServer(SimulatedMainchain(),
                                sig_backend=self.serving)
        self.server.start()
        self.client = RpcReplicaBackend.dial(*self.server.address,
                                             timeout=600.0)

    def request(self, keyed=False, again=False, method="verifyCommittees"):
        """One keyless request, or one of `_keyed_committees` under row
        keys never sent before (`again`: under the last keyed request's),
        or one `shard_dasVerify` of `_das_samples`, or one row of a
        `_plain_calls` method, its verdicts checked; returns once the
        server has booked it (it books after it flushes the response)."""
        booked = metrics.timer(f"rpc/{method}/server_time")
        count = booked.count
        call, args, want = (self.client.bls_verify_committees, self.args,
                            self.want)
        if method in self.plain:
            name, args, want = self.plain[method]
            call = getattr(self.client, name)
        elif method == "dasVerify":
            call, (*args, want) = self.client.das_verify_samples, self.das
        elif keyed:
            self.keyed_sent += not again
            *args, want = self.keyed
            args.append([("stage", self.keyed_sent, r) for r in range(4)])
        t0 = time.monotonic()
        assert call(*args) == want
        latency = time.monotonic() - t0
        deadline = time.monotonic() + 10.0
        while booked.count == count and time.monotonic() < deadline:
            time.sleep(0.001)
        assert booked.count == count + 1
        return latency

    def close(self):
        self.client.close()
        self.server.stop()
        self.serving.close()


@pytest.fixture(scope="module")
def served():
    tracing.GC_CLOCK.install()
    box = Served()
    try:
        box.request()   # the first compiles, or reads the compile cache
        yield box
    finally:
        box.close()


@pytest.fixture(scope="module")
def untraced(served):
    """One request with the tracer off, between two `shard_metrics`."""
    assert not tracing.TRACER.enabled
    spans = tracing.TRACER.spans_recorded
    before = served.client.metrics()
    latency = served.request()
    after = served.client.metrics()
    return {"before": before, "after": after, "latency_s": latency,
            "spans": tracing.TRACER.spans_recorded - spans}


def _traced_request(served, **kind):
    tracing.enable(ring_spans=4096)
    tracing.TRACER.clear()
    try:
        served.request(**kind)
        return tracing.TRACER.recent_spans()
    finally:
        tracing.disable()
        tracing.TRACER.clear()


@pytest.fixture(scope="module")
def traced(served, untraced):
    """The spans of one request with the tracer on (client and server
    share the process, so one ring holds both ends)."""
    return _traced_request(served)


@pytest.fixture(scope="module")
def das_untraced(served, untraced):
    """One `shard_dasVerify` request with the tracer off, between two
    `shard_metrics`, after one that compiled or read the cache."""
    served.request(method="dasVerify")
    before = served.client.metrics()
    latency = served.request(method="dasVerify")
    after = served.client.metrics()
    return {"before": before, "after": after, "latency_s": latency}


@pytest.fixture(scope="module")
def das_traced(served, das_untraced):
    """The spans of one such request with the tracer on."""
    return _traced_request(served, method="dasVerify")


def _plain_untraced(method):
    @pytest.fixture(scope="module", name=f"{method}_untraced")
    def fixture(served, untraced):
        """One request of an operation no cell drives with the tracer
        off, between two `shard_metrics`, after one that compiled or
        read the cache."""
        served.request(method=method)
        before = served.client.metrics()
        latency = served.request(method=method)
        after = served.client.metrics()
        return {"before": before, "after": after, "latency_s": latency}
    return fixture


for _method in PLAIN:
    globals()[f"{_method}_untraced"] = _plain_untraced(_method)


@pytest.fixture(scope="module")
def keyed(served, untraced):
    """One request whose four row keys are new, so that four line tables
    miss, with the tracer off, between two `shard_metrics`."""
    served.request(keyed=True)   # compiles the table-fed kernels' shapes
    before = served.client.metrics()
    latency = served.request(keyed=True)
    after = served.client.metrics()
    return {"before": before, "after": after, "latency_s": latency}


@pytest.fixture(scope="module")
def keyed_again(served, keyed):
    """One request under the row keys of the keyed request before it:
    the batch memo holds its tables."""
    before = served.client.metrics()
    served.request(keyed=True, again=True)
    return {"before": before, "after": served.client.metrics()}


@pytest.fixture(scope="module")
def keyed_traced(served, keyed):
    """The spans of one such request with the tracer on."""
    return _traced_request(served, keyed=True)


def _bare_socket_request(served, args, want, listed=False):
    """One request over a bare socket with no `trace` envelope, as the
    benchmark's client sends it; `listed`: every row as the coordinate
    lists of a client older than the packed row. Returns once the
    server has booked it."""
    from gethsharding_tpu.rpc import codec

    messages, sig_rows, pk_rows, *keys = args
    if listed:
        sigs = [[codec.enc_g1(p) for p in row] for row in sig_rows]
        pks = [[codec.enc_g2(p) for p in row] for row in pk_rows]
    else:
        sigs, pks = codec.enc_g1_rows(sig_rows), codec.enc_g2_rows(pk_rows)
    frame = {"jsonrpc": "2.0", "id": 1, "method": "shard_verifyCommittees",
             "params": [[codec.enc_bytes(m) for m in messages], sigs, pks,
                        *(codec.enc_pk_row_keys(k) for k in keys)]}
    booked = metrics.timer(RPC + "server_time")
    count = booked.count
    with socket.create_connection(served.server.address,
                                  timeout=600.0) as sock:
        sock.sendall((json.dumps(frame) + "\n").encode())
        reply = json.loads(sock.makefile("rb").readline())
    assert [bool(b) for b in reply["result"]] == want
    deadline = time.monotonic() + 10.0
    while booked.count == count and time.monotonic() < deadline:
        time.sleep(0.001)
    assert booked.count == count + 1


@pytest.fixture(scope="module")
def traced_server_alone(served, traced):
    """The spans of one request whose caller is not traced, as the
    benchmark's client is not: the frame goes over a bare socket with no
    `trace` envelope, to a server with the tracer on."""
    tracing.enable(ring_spans=4096)
    tracing.TRACER.clear()
    try:
        _bare_socket_request(served, served.args, served.want)
        return tracing.TRACER.recent_spans()
    finally:
        tracing.disable()
        tracing.TRACER.clear()


@pytest.fixture(scope="module")
def listed(served, untraced):
    """One request of an older client, every row as coordinate lists,
    with the tracer off, between two `shard_metrics`."""
    before = served.client.metrics()
    _bare_socket_request(served, served.args, served.want, listed=True)
    return {"before": before, "after": served.client.metrics()}


def _delta(snap, name, field="count"):
    def read(side):
        row = snap[side].get(name) or {}
        if field == "count":
            return row.get("count", 0)
        return row.get("mean_s", 0.0) * row.get("count", 0)
    return read("after") - read("before")


# == the registry: always on ================================================


# (method, the fixture that holds one untraced request of it): the packed
# rows `RpcReplicaBackend` sends, the DAS plane, the three operations no
# cell drives; an older client's listed rows over a bare socket
CALLED = [(m, METHODS[m]["untraced"]) for m in sorted(METHODS)] \
    + [(m, f"{m}_untraced") for m in sorted(PLAIN)]
UNTRACED = CALLED + [("verifyCommittees", "listed")]


@pytest.mark.parametrize("method, fixture", UNTRACED)
@pytest.mark.parametrize("name", STAGE_TIMERS)
def test_one_request_counts_once_in_every_stage_timer(request, method,
                                                      fixture, name):
    snap = request.getfixturevalue(fixture)
    assert _delta(snap, _named(name, method)) == 1


@pytest.mark.parametrize("method, fixture", UNTRACED)
@pytest.mark.parametrize("whole", sorted(WHOLES))
def test_each_whole_covers_its_parts(request, method, fixture, whole):
    snap = request.getfixturevalue(fixture)
    assert _delta(snap, _named(whole, method)) == 1
    parts = sum(_delta(snap, _named(part, method), "total")
                for part in WHOLES[whole])
    # a snapshot rounds a mean to the microsecond
    assert parts <= _delta(snap, _named(whole, method), "total") \
        + 1e-5 * len(WHOLES[whole])


# == the multiproof's host coefficients and device MSMs (ISSUE 39) ==========


def test_poly_coeffs_time_nests_in_host_marshal_time(dasPolyVerify_untraced):
    snap = dasPolyVerify_untraced
    assert _delta(snap, "sig/poly_coeffs_time") == 1
    # a snapshot rounds a mean to the microsecond
    assert 0 < _delta(snap, "sig/poly_coeffs_time", "total") \
        <= _delta(snap, "sig/host_marshal_time", "total") + 1e-5


def test_device_msm_rows_counts_the_rows_of_a_dispatch(
        dasPolyVerify_untraced):
    snap = dasPolyVerify_untraced
    assert _delta(snap, "serving/das_poly_verify/dispatches") == 1
    assert _delta(snap, "das/poly/device_msm_rows") == 1


def test_device_msm_rows_is_zero_for_an_all_malformed_dispatch(
        served, dasPolyVerify_untraced):
    """An off-curve commitment fails its decode on the host: the row
    is a False and no MSM of it runs on the device (the shape is the
    honest row's, so nothing compiles)."""
    _, (_, *rest), _ = served.plain["dasPolyVerify"]
    before = served.client.metrics()
    assert served.client.das_verify_multiproofs([b"\x07" * 64],
                                                *rest) == [False]
    snap = {"before": before, "after": served.client.metrics()}
    assert _delta(snap, "serving/das_poly_verify/dispatches") == 1
    assert _delta(snap, "das/poly/device_msm_rows") == 0


# == the caller's half (ISSUE 37) ===========================================


@pytest.mark.parametrize("method, fixture", CALLED)
@pytest.mark.parametrize("name", CLIENT_TIMERS)
def test_one_call_counts_once_in_every_client_timer(request, method,
                                                    fixture, name):
    snap = request.getfixturevalue(fixture)
    assert _delta(snap, _named(name, method)) == 1


@pytest.mark.parametrize("method, fixture", CALLED)
def test_the_callers_clock_covers_its_stages(request, method, fixture):
    snap = request.getfixturevalue(fixture)

    def total(name):
        return _delta(snap, _named(name, method), "total")

    # encode, dumps, send and wait follow one another on the caller's
    # thread, inside its clock; a snapshot rounds a mean to the microsecond
    assert 0 < sum(total(name) for name in CLIENT_PARTS) \
        <= snap["latency_s"] + 1e-5 * len(CLIENT_PARTS)
    wait = total("caller/rpc/client/{m}/wait_time")
    # the reply's line is read after the request is sent
    assert 0 < total("caller/rpc/client/{m}/reply_time") <= wait + 2e-5
    # the server works while the caller waits. Two threads read these
    # clocks: the line can be complete a thread switch before the
    # caller's flush returns, and the server reads its last clock a
    # switch after the flush that woke the caller
    assert total("{rpc}server_time") <= wait + 0.05
    # recv_time lies before server_time and is no part of it
    assert total("{rpc}recv_time") + total("{rpc}server_time") \
        <= snap["latency_s"] + 0.05


def test_a_codec_that_raises_leaves_no_pending_slot(served):
    """What the codec raises reaches the caller, no slot stays pending
    (the codec runs before the slot is made, and whatever fails after
    it is reclaimed in `call`'s `finally`), and the connection's next
    call is served."""
    client = served.client.client

    def broken():
        raise ValueError("no such point")

    pending = len(client._pending)
    with pytest.raises(ValueError, match="no such point"):
        client.call("shard_verifyCommittees", encode=broken)
    assert len(client._pending) == pending
    assert client.call("shard_blockNumber") >= 0


def _frame(served, method, rid, trace=None):
    """One request's line as `RPCClient.call` would write it."""
    from gethsharding_tpu.rpc import codec

    if method == "dasVerify":
        params = list(codec.enc_das_call(*served.das[:4]))
    else:
        messages, sig_rows, pk_rows = served.args
        params = [[codec.enc_bytes(m) for m in messages],
                  codec.enc_g1_rows(sig_rows), codec.enc_g2_rows(pk_rows)]
    frame = {"jsonrpc": "2.0", "id": rid, "method": f"shard_{method}",
             "params": params}
    if trace is not None:
        frame["trace"] = {"trace_id": trace[0], "span_id": trace[1]}
    return (json.dumps(frame) + "\n").encode()


@pytest.mark.parametrize("method", sorted(METHODS))
def test_recv_time_starts_at_a_frames_first_bytes(request, served, method):
    """Two frames written in one `sendall`, a pause after the connect:
    `recv_time` is observed once a request, holds none of the idle wait
    before the first frame, is near 0 for the second, which was in the
    buffer or on its way, and `frame_bytes` counts both lines. A caller
    that names a span gets `recv_time` under it."""
    request.getfixturevalue(METHODS[method]["untraced"])    # compiled
    recv = metrics.timer(f"rpc/{method}/recv_time")
    booked = metrics.timer(f"rpc/{method}/server_time")
    frame_bytes = metrics.counter(f"rpc/{method}/frame_bytes")
    frames = [_frame(served, method, rid, trace=(7700 + rid, 8800 + rid))
              for rid in (1, 2)]
    counts = recv.count, booked.count, frame_bytes.value
    tracing.enable(ring_spans=4096)
    tracing.TRACER.clear()
    try:
        with socket.create_connection(served.server.address,
                                      timeout=600.0) as sock:
            time.sleep(0.3)
            sock.sendall(b"".join(frames))
            lines = sock.makefile("rb")
            replies = [json.loads(lines.readline()) for _ in frames]
        deadline = time.monotonic() + 10.0
        while booked.count < counts[1] + 2 and time.monotonic() < deadline:
            time.sleep(0.001)
        spans = [s for s in tracing.TRACER.recent_spans()
                 if s["name"] == f"rpc/{method}/recv_time"]
    finally:
        tracing.disable()
        tracing.TRACER.clear()
    want = served.das[4] if method == "dasVerify" else served.want
    assert sorted(r["id"] for r in replies) == [1, 2]
    assert all([bool(b) for b in r["result"]] == want for r in replies)
    assert recv.count == counts[0] + 2 and booked.count == counts[1] + 2
    assert frame_bytes.value - counts[2] == sum(map(len, frames))
    first, second = sorted(spans, key=lambda s: s["start"])
    assert (first["trace"], first["parent"]) == (7701, 8801)
    assert (second["trace"], second["parent"]) == (7702, 8802)
    assert [s["tags"]["bytes"] for s in (first, second)] \
        == [len(f) for f in frames]
    assert first["end"] - first["start"] < 0.25    # none of the pause
    assert second["end"] - second["start"] < 0.05


def test_metrics_lays_the_callers_rows_beside_the_replicas(served, untraced):
    """`RpcReplicaBackend.metrics()`: every row of `shard_metrics`
    untouched, the calling process's `rpc/client/*` rows only under
    `caller/`, and a router's sweep folds none of them."""
    from gethsharding_tpu.fleet.router import (CALLER_PREFIX, FleetRouter,
                                               Replica)

    snap = untraced["after"]
    mine = {name for name in snap if name.startswith(CALLER_PREFIX)}
    assert mine and all(
        name.startswith(CALLER_PREFIX + "rpc/client/") for name in mine)
    # client and server share this process's registry: the server's
    # snapshot holds the same timers under their own names, as a
    # frontend's would hold its own, and no `caller/` row
    served_rows = served.client.client.call("shard_metrics")
    assert not any(name.startswith(CALLER_PREFIX) for name in served_rows)
    assert set(snap) - mine <= set(served_rows)
    assert {name[len(CALLER_PREFIX):] for name in mine} <= set(served_rows)
    row = snap["rpc/verifyCommittees/server_time"]
    assert row["type"] == "timer" and row["count"] >= 1
    registry = metrics.Registry()
    router = FleetRouter([Replica("r0", served.client,
                                  health=served.client.health, probe=None,
                                  registry=registry)],
                         health_interval_s=0.0, registry=registry)
    router.refresh(force=True)
    folded = set(registry.snapshot())
    assert "fleet/replica/r0/sig/device_time/count" in folded
    assert not any("caller/" in name or "rpc/client" in name
                   for name in folded)


@pytest.mark.parametrize("fixture, rows", [("untraced", 2), ("listed", 0),
                                           ("keyed", 4)])
def test_the_server_counts_the_rows_that_arrived_packed(request, fixture,
                                                        rows):
    snap = request.getfixturevalue(fixture)
    assert _delta(snap, RPC + "packed_rows") == rows
    # no packed coordinate reached P, and no listed row lay beside a
    # packed one: nothing took the integer entry of a packed dispatch
    assert _delta(snap, "sig/marshal/int_rows") == 0


def test_forged_and_empty_rows_read_the_same_through_both_wire_forms(
        served, keyed_again):
    """Four rows under new row keys (the shapes the keyed request keeps
    warm; after `keyed_again`, whose batch memo these requests replace):
    a good row, a forged one, an EMPTY one, a good one, through the
    packed and the listed wire, against the scalar backend."""
    from gethsharding_tpu.sigbackend import PythonSigBackend

    msgs, sig_rows, pk_rows, _ = _keyed_committees()
    sig_rows, pk_rows = [list(r) for r in sig_rows], list(pk_rows)
    sig_rows[1][2] = sig_rows[0][2]     # a real vote, on another header
    sig_rows[2], pk_rows[2] = [], []
    want = PythonSigBackend().bls_verify_committees(msgs, sig_rows, pk_rows)
    assert want == [True, False, False, True]
    packed = metrics.counter(RPC + "packed_rows")

    def keys(wire):
        return [("stage-forged", wire, r) for r in range(4)]

    before = packed.value
    booked = metrics.timer(RPC + "server_time")
    count = booked.count
    assert served.client.bls_verify_committees(msgs, sig_rows, pk_rows,
                                               keys("packed")) == want
    assert packed.value - before == 4       # the empty row is the row "0x"
    # the server books a request after it has answered it: let it, or
    # the bare request below counts this booking as its own
    deadline = time.monotonic() + 10.0
    while booked.count == count and time.monotonic() < deadline:
        time.sleep(0.001)
    _bare_socket_request(served, (msgs, sig_rows, pk_rows, keys("listed")),
                         want, listed=True)
    assert packed.value - before == 4


@pytest.mark.parametrize("group, point_bytes", [("sig_rows", 64),
                                                ("pk_rows", 128)])
def test_a_packed_row_of_bad_length_gets_a_malformed_lists_error(
        served, group, point_bytes):
    """No whole number of points: the error response a malformed list
    gets, and the connection's next request is served."""
    from gethsharding_tpu.rpc import codec
    from gethsharding_tpu.rpc.client import RPCClient, RPCError

    messages, sig_rows, pk_rows = served.args
    good = {"messages": [codec.enc_bytes(m) for m in messages],
            "sig_rows": codec.enc_g1_rows(sig_rows),
            "pk_rows": codec.enc_g2_rows(pk_rows)}
    client = RPCClient(*served.server.address, timeout=600.0)
    try:
        codes = []
        for bad in ("0x" + "00" * (point_bytes + 1), [["0x1"]]):
            params = dict(good)
            params[group] = [params[group][0], bad]
            with pytest.raises(RPCError) as err:
                client.call("shard_verifyCommittees", *params.values())
            codes.append(err.value.code)
        assert codes[0] == codes[1]
        assert client.call("shard_verifyCommittees",
                           *good.values()) == served.want
    finally:
        client.close()


@pytest.mark.parametrize("name", LINE_STAGES)
def test_a_line_table_miss_enters_each_line_stage_once(untraced, keyed,
                                                       name):
    assert _delta(untraced, name) == 0      # no row keys: no line table
    assert _delta(keyed, name) == 1


@pytest.mark.parametrize("name", LINE_STAGES)
def test_a_memo_hit_enters_no_line_stage(keyed_again, name):
    assert _delta(keyed_again, "sig/transfer_time") == 1
    assert _delta(keyed_again, name) == 0
    assert _delta(keyed_again, "jax/pk_device_cache/misses") == 0
    assert _delta(keyed_again, "jax/wire/g2_bytes") == 0


def test_the_transfer_stage_covers_the_line_stages(keyed):
    assert _delta(keyed, "sig/transfer_time") == 1
    parts = sum(_delta(keyed, part, "total") for part in LINE_STAGES)
    assert 0 < parts <= _delta(keyed, "sig/transfer_time", "total") + 2e-5
    assert _delta(keyed, "jax/pk_device_cache/misses") == 4
    assert _delta(keyed, "jax/wire/g2_bytes") > 0


def test_tracer_off_records_no_span_and_stage_still_feeds_its_timer(
        served, untraced):
    assert untraced["spans"] == 0
    timer = metrics.Timer()
    recorded = tracing.TRACER.spans_recorded
    with tracing.stage("sig/test_stage", timer) as clock:
        time.sleep(0.001)
    assert timer.count == 1 and clock.seconds >= 0.001
    assert timer.mean() == clock.seconds
    # `RPCClient.call` alone: every clock of its, and no span
    clocks = [metrics.timer(f"rpc/client/blockNumber/{leaf}_time")
              for leaf in ("dumps", "send", "wait", "reply")]
    counts = [c.count for c in clocks]
    assert served.client.client.call("shard_blockNumber") >= 0
    assert [c.count for c in clocks] == [n + 1 for n in counts]
    assert tracing.TRACER.spans_recorded == recorded


# == the tracer: one trace id from the client to the pull ====================


def _request_trace(spans, method="verifyCommittees"):
    handlers = [s for s in spans if s["name"] == f"rpc/shard_{method}"]
    assert len(handlers) == 1
    trace_id = handlers[0]["trace"]
    return trace_id, [s for s in spans if s["trace"] == trace_id]


@pytest.mark.parametrize("method", sorted(METHODS))
@pytest.mark.parametrize("name", [
    "rpc/client/shard_{m}", "rpc/client/{m}/encode_time",
    "rpc/client/{m}/dumps_time", "rpc/client/roundtrip",
    "rpc/client/{m}/send_time", "rpc/client/{m}/reply_time",
    "rpc/client/decode", "{rpc}recv_time",
    "{rpc}server_time", "rpc/shard_{m}", "{rpc}admit",
    "{rpc}parse_time", "{rpc}decode_time", "{rpc}respond",
    "serving/{op}/request", "serving/{op}/device_dispatch",
    "serving/{op}/dispatch", "{dispatch}", *SIG_SPANS])
def test_one_trace_id_holds_the_request_down_to_the_pull(request, method,
                                                         name):
    spans = request.getfixturevalue(METHODS[method]["traced"])
    _, mine = _request_trace(spans, method)
    name = name.format(m=method, rpc=f"rpc/{method}/", **METHODS[method])
    # a traced stretch is one span, under the stage's own name
    assert [s["name"] for s in mine].count(name) == 1, sorted(
        s["name"] for s in mine)


@pytest.mark.parametrize("method", sorted(METHODS))
def test_no_dispatch_span_is_a_trace_of_its_own(request, method):
    spans = request.getfixturevalue(METHODS[method]["traced"])
    trace_id, _ = _request_trace(spans, method)
    strays = [s["name"] for s in spans if s["trace"] != trace_id
              and s["name"].startswith(("jax/", "sig/", "serving/"))]
    assert strays == []


@pytest.mark.parametrize("method", sorted(METHODS))
def test_the_chain_of_parents_runs_from_the_client_to_the_stages(request,
                                                                 method):
    spans = request.getfixturevalue(METHODS[method]["traced"])
    _, mine = _request_trace(spans, method)
    by_id = {s["span"]: s for s in mine}
    one = {s["name"]: s for s in mine}
    rpc, handler = f"rpc/{method}/", f"rpc/shard_{method}"
    op, dispatch = METHODS[method]["op"], METHODS[method]["dispatch"]

    def parent(name):
        return by_id[one[name]["parent"]]["name"]

    client = f"rpc/client/{method}/"
    for name in (client + "encode_time", client + "dumps_time",
                 "rpc/client/roundtrip"):
        assert parent(name) == f"rpc/client/shard_{method}"
    # the envelope names the roundtrip, not the call's span: the server
    # works inside the roundtrip, between the send and the reply
    for name in (client + "send_time", rpc + "recv_time",
                 rpc + "server_time", client + "reply_time"):
        assert parent(name) == "rpc/client/roundtrip"
    assert parent("rpc/client/decode") == client + "reply_time"
    assert one[client + "send_time"]["tags"]["bytes"] \
        == one[rpc + "recv_time"]["tags"]["bytes"] > 0
    for name in (handler, rpc + "admit", rpc + "parse_time",
                 rpc + "respond"):
        assert parent(name) == rpc + "server_time"
    assert parent(rpc + "decode_time") == handler
    assert parent(f"serving/{op}/request") == handler
    assert parent(f"serving/{op}/device_dispatch") == f"serving/{op}/request"
    assert parent(f"serving/{op}/dispatch") == f"serving/{op}/device_dispatch"
    assert one[f"serving/{op}/device_dispatch"]["tags"]["dispatch_span"] \
        == one[f"serving/{op}/dispatch"]["span"]
    for name in ("sig/host_marshal_time", "sig/transfer_time", dispatch):
        assert parent(name) == f"serving/{op}/dispatch"
    for name in ("sig/launch_time", "sig/block_time", "sig/pull_time"):
        assert parent(name) == dispatch


def test_the_line_stages_are_spans_under_the_transfer_stage(keyed_traced):
    _, mine = _request_trace(keyed_traced)
    by_id = {s["span"]: s for s in mine}
    one = {s["name"]: s for s in mine}
    for name in LINE_STAGES:
        assert [s["name"] for s in mine].count(name) == 1
        assert by_id[one[name]["parent"]]["name"] == "sig/transfer_time"
    tags = one["jax/bls_committee_dispatch"]["tags"]
    assert (tags["line_miss_rows"], tags["line_hit_rows"]) == (4, 0)


@pytest.mark.parametrize("caller, method", [
    ("traced", "verifyCommittees"), ("keyed_traced", "verifyCommittees"),
    ("das_traced", "dasVerify")])
def test_every_child_lies_inside_its_parents_interval(request, caller,
                                                      method):
    _, mine = _request_trace(request.getfixturevalue(caller), method)
    by_id = {s["span"]: s for s in mine}
    checked = 0
    for span in mine:
        # the wake is recorded after its request by design
        if span["name"].endswith("/future_wake"):
            continue
        parent = by_id.get(span["parent"])
        if parent is None:
            continue
        end = parent["end"]
        if span["name"].endswith("/server_time"):
            # it ends a clock read after the flush that lets the
            # client's roundtrip end
            end = span["end"]
        assert parent["start"] <= span["start"] <= span["end"] <= end, (
            span["name"], parent["name"])
        checked += 1
    assert checked >= 16


@pytest.mark.parametrize("caller, method, root, spans", [
    ("traced", "verifyCommittees", "rpc/client/shard_verifyCommittees", 20),
    ("keyed_traced", "verifyCommittees",
     "rpc/client/shard_verifyCommittees", 22),
    ("das_traced", "dasVerify", "rpc/client/shard_dasVerify", 20),
    ("traced_server_alone", "verifyCommittees", RPC + "server_time", 16)])
def test_the_self_times_of_a_real_request_add_up_to_its_root(
        request, caller, method, root, spans):
    """fleettrace's walk over the spans of a real request: every span
    hangs in one tree under `root`, and no stretch of it is booked
    twice (an enclosing span beside the span it encloses would be)."""
    from gethsharding_tpu.fleettrace.critical_path import attribute

    _, mine = _request_trace(request.getfixturevalue(caller), method)
    attr = attribute(mine)
    assert attr["root"] == root
    assert attr["orphan_spans"] == 0 and attr["spans"] >= spans
    booked = sum(attr["segments"].values())
    # the wake overhangs its request (critical_path's docstring), the
    # server's last clock read its roundtrip
    assert attr["total_s"] - 1e-6 <= booked <= 1.02 * attr["total_s"] + 0.002
    if caller != "traced_server_alone":
        assert attr["segments"]["wire"] < 0.5 * attr["total_s"]


def test_a_stage_span_has_its_timers_bounds():
    timer = metrics.Timer()
    tracing.enable()
    tracing.TRACER.clear()
    try:
        with tracing.span("outer") as outer:
            with tracing.stage("sig/test_stage", timer) as clock:
                time.sleep(0.001)
        spans = {s["name"]: s for s in tracing.TRACER.recent_spans()}
    finally:
        tracing.disable()
        tracing.TRACER.clear()
    mine = spans["sig/test_stage"]
    assert mine["end"] - mine["start"] == clock.seconds == timer.mean()
    assert (mine["trace"], mine["parent"]) == (outer.trace_id, outer.span_id)


def test_a_stage_is_a_profiler_annotation_where_jax_is_imported():
    import jax

    assert isinstance(tracing.annotation("sig/test_stage"),
                      jax.profiler.TraceAnnotation)


# == the collector ==========================================================


def test_gc_clock_counts_every_collection_and_spans_the_full_ones(served):
    counter = metrics.DEFAULT_REGISTRY.get(tracing.GC_CLOCK.COUNTER)
    tracing.enable()
    tracing.TRACER.clear()
    try:
        with tracing.span("outer") as outer:
            # read before the allocations: they trip young collections
            # of hundreds of objects each, where the one asked for below
            # may find the young generation empty and take under 1 us
            before = counter.value
            junk = [[i] for i in range(20000)]
            junk.append(junk)
            del junk
            gc.collect(0)
            assert counter.value > before     # young collections count
            gc.collect()
            with tracing.stage("sig/test_stage", metrics.Timer()):
                pass    # a traced stage records what the callback put aside
        spans = [s for s in tracing.TRACER.recent_spans()
                 if s["name"] == "runtime/gc"]
    finally:
        tracing.disable()
        tracing.TRACER.clear()
    assert spans and all(s["tags"]["generation"] == 2 for s in spans)
    assert (spans[-1]["trace"], spans[-1]["parent"]) \
        == (outer.trace_id, outer.span_id)
    # a snapshot may run the collector while it holds the counter's
    # lock, and again over the rows it reads after the counter's
    low = counter.value
    assert low <= metrics.DEFAULT_REGISTRY.snapshot()[
        tracing.GC_CLOCK.COUNTER]["count"] <= counter.value


def test_a_traced_request_dies_with_its_last_reference(served):
    """With the tracer on the request's future carries what the wake
    span needs and never the request, which names the future: such a
    loop kept every decoded point of the batch until a full collection
    (PR 27's first `--trace-out` window)."""
    from gethsharding_tpu.serving.queue import Request

    tracing.enable()
    gc.collect()
    gc.set_debug(gc.DEBUG_SAVEALL)     # what a collection finds, it keeps
    try:
        for _ in range(2):  # the server's threads hold on to their last
            served.request()
        gc.collect()
        found = [o for o in gc.garbage if isinstance(o, (Request, bls.Fp2))]
    finally:
        gc.set_debug(0)
        gc.garbage.clear()
        tracing.disable()
        tracing.TRACER.clear()
    assert found == []


# == the benchmark's per-layer metric files =================================

with open(os.path.join(REPO, "BENCHMARK.json")) as _src:
    PER_LAYER = [m["name"] for m in json.load(_src)["per_layer"]]


@pytest.mark.parametrize("name", PER_LAYER)
def test_every_per_layer_metric_reads_a_number_from_two_snapshots(
        request, name):
    # what only a line-table miss writes is read over the keyed request,
    # what only `shard_dasVerify` or `shard_dasPolyVerify` writes over a
    # request of that method
    poly = name.startswith("das_poly_")
    das = name.startswith("das_") and not poly
    snap = request.getfixturevalue(
        "keyed" if name.startswith("line_") else
        "dasPolyVerify_untraced" if poly else
        "das_untraced" if das else "untraced")
    op = (PLAIN["dasPolyVerify"] if poly
          else METHODS["dasVerify" if das else "verifyCommittees"]["op"])
    bench = os.path.join(REPO, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import run

    spec = run.read_json("layer_metrics", name + ".json")
    assert spec["name"] == name
    # the device trace is the chip's; its one metric reads this stand-in
    trace = {"busy_s": 0.001, "counts": {f"serving/{op}/dispatches": 1}}
    value = run.layer_metric(spec, op, snap["before"], snap["after"],
                             client_mean_ms=1e3 * snap["latency_s"],
                             trace=trace)
    assert isinstance(value, (int, float)) and value >= 0.0, (name, value)
    if name == "line_miss_rows":
        assert value == 4.0
    if name == "packed_rows":
        assert value == 2.0         # both rows of the request
    if name == "das_chunk_bytes":
        assert value == 4 * 4096    # bucket 4, a 4,096-byte chunk a row
    if name == "das_poly_device_msm_rows":
        assert value == 1.0         # the request's one row


# == the kernels' names =====================================================


@pytest.fixture(scope="module")
def lowered_committee_kernel():
    import jax
    import jax.numpy as jnp

    from gethsharding_tpu.ops import bn256_jax
    from gethsharding_tpu.ops.limb import NLIMBS

    def plane(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    rows, votes = 1, 1
    return jax.jit(bn256_jax.bls_aggregate_verify_committee_batch).lower(
        plane(rows, NLIMBS), plane(rows, NLIMBS),
        plane(rows, votes, NLIMBS), plane(rows, votes, NLIMBS),
        plane(rows, votes, dtype=jnp.bool_),
        plane(rows, votes, 2, NLIMBS), plane(rows, votes, 2, NLIMBS),
        plane(rows, votes, dtype=jnp.bool_), plane(rows, dtype=jnp.bool_),
    ).as_text(debug_info=True)


@pytest.mark.parametrize("scope", ["bls/g1_aggregate", "bls/g2_aggregate",
                                   "bls/miller", "bls/final_exp"])
def test_the_committee_kernels_stages_are_named_in_the_lowered_text(
        lowered_committee_kernel, scope):
    assert scope in lowered_committee_kernel
