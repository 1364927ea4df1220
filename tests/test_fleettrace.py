"""fleettrace: cross-process trace assembly, tail-based sampling, and
critical-path attribution (gethsharding_tpu/fleettrace/).

Contracts:

- EXPORT PLANE: the tracer's bounded export buffer stages every
  finished span, evicts oldest-first under pressure with an HONEST
  cumulative drop count, and the span codec roundtrips records
  positionally (exotic tag values coerced, never poisoning a batch).
- ASSEMBLY: the collector rebases each batch onto its own wall clock
  via the ``clock_offset_us`` + handshake ``skew_us`` anchors, groups
  by trace id across producer pids, applies pending marks, flags
  traces fed by lossy sources incomplete, and evicts oldest over the
  cap.
- TAIL SAMPLING: retention reasons are deterministic — marked traces
  always kept, the hash sample makes the same per-trace decision on
  every collector, the top latency quantile is kept once history
  accumulates, everything else is attributed THEN dropped.
- CRITICAL PATH: self-times over a span tree telescope to the root's
  duration; hedge-wasted duplicate work is reported beside the table,
  outside the identity.
- WIRE: the RPC response envelope carries the handler's exact span id
  (``traceCtx``), so a caller's client span links to the remote
  handler span unambiguously.
- BOOT: `boot_collector` assembles this process's own spans end to
  end (in-proc exporter -> collector -> attribution/exemplars) and
  `shutdown` unwinds every hook.
"""

import time

import pytest

from gethsharding_tpu import metrics, tracing
from gethsharding_tpu.fleettrace.collector import TraceCollector
from gethsharding_tpu.fleettrace.critical_path import (
    HEDGE_WASTED,
    SEGMENTS,
    attribute,
    segment_for,
)
from gethsharding_tpu.rpc import codec


def _registry() -> metrics.Registry:
    return metrics.Registry()


def _tracer(ring: int = 64) -> tracing.Tracer:
    tracer = tracing.Tracer(ring_spans=ring, registry=_registry())
    tracer.enabled = True
    return tracer


def _row(name: str, trace: int, span: int, parent, start: float,
         end: float, tags=None) -> list:
    """One wire-format span row (what `codec.enc_spans` emits)."""
    return [name, trace, span, parent, start, end, 1, tags]


def _payload(rows, pid=100, label="r0", offset_us=0.0, skew_us=0.0,
             dropped=0) -> dict:
    return {"pid": pid, "label": label, "clock_offset_us": offset_us,
            "skew_us": skew_us, "dropped": dropped, "spans": rows}


# == the export plane =======================================================


def test_export_buffer_drains_and_counts_evictions():
    """The staging buffer is bounded: under exporter lag the OLDEST
    staged spans are evicted and counted cumulatively, the drop count
    rides every drain, and the ring-pressure gauge tracks fill."""
    tracer = _tracer(ring=256)
    tracer.enable_export(buffer_spans=4)
    for i in range(10):
        tracer.record(f"s{i}", 0.0, 0.001, trace_id=1)
    batch, dropped = tracer.drain_export(max_spans=512)
    assert [r["name"] for r in batch] == ["s6", "s7", "s8", "s9"]
    assert dropped == 6 and tracer.export_dropped == 6
    assert tracer.registry.counter("trace/export_dropped").value == 6
    # pressure gauge: 10 spans in a 256 ring
    assert tracer.registry.gauge(
        "trace/ring_pressure").value == pytest.approx(10 / 256)
    # cumulative: a later eviction round adds, never resets
    for i in range(5):
        tracer.record(f"t{i}", 0.0, 0.001, trace_id=1)
    batch, dropped = tracer.drain_export(max_spans=2)
    assert len(batch) == 2 and dropped == 7
    # disable tears the buffer down; drains report the final count
    tracer.disable_export()
    assert tracer.drain_export() == ([], 7)


def test_ring_eviction_is_counted():
    """Ring overflow (a finished span nobody exported is overwritten)
    is an alert, not silence: ``trace/dropped`` counts it."""
    tracer = _tracer(ring=8)
    for i in range(12):
        tracer.record(f"s{i}", 0.0, 0.001)
    assert tracer.spans_dropped == 4
    assert tracer.registry.counter("trace/dropped").value == 4
    assert tracer.registry.gauge("trace/ring_pressure").value == 1.0


def test_span_codec_roundtrips_and_coerces_exotic_tags():
    tracer = _tracer()
    tracer.enable_export()
    tracer.record("rpc/shard_x", 1.5, 2.25, trace_id=7, parent_id=3,
                  tags={"klass": "interactive", "rows": 4,
                        "exotic": b"\x00bytes"})
    tracer.record("fleet/route", 0.0, 1.0)
    batch, _ = tracer.drain_export()
    rows = codec.enc_spans(batch)
    back = codec.dec_spans(rows)
    assert back[0]["name"] == "rpc/shard_x"
    assert back[0]["trace"] == 7 and back[0]["parent"] == 3
    assert back[0]["start"] == 1.5 and back[0]["end"] == 2.25
    assert back[0]["tags"]["klass"] == "interactive"
    assert back[0]["tags"]["rows"] == 4
    # non-JSON tag values ship as repr, not a serialization error
    assert back[0]["tags"]["exotic"] == repr(b"\x00bytes")
    assert back[1]["parent"] is None and back[1]["tags"] == {}
    assert codec.enc_span_tags(None) is None


# == assembly + rebasing ====================================================


def test_collector_rebases_and_assembles_across_processes():
    """Two producers with different clock anchors feed ONE trace: the
    collector lands both on its wall clock (offset + handshake skew),
    the tree attributes across both pids, and a marked trace is
    retained with its mark."""
    collector = TraceCollector(_registry(), max_traces=64, linger_s=0.0,
                               sample=0.0)
    collector.mark_trace(11, "hedged")  # mark BEFORE the spans arrive
    # frontend (pid 100): anchor 1 s — client span [10.0, 10.1]
    collector.ingest_payload(_payload(
        [_row("rpc/client/shard_x", 11, 1, None, 10.0, 10.1,
              {"klass": "interactive"})],
        pid=100, label="fe", offset_us=1e6))
    # replica (pid 200): anchor 2 s + 0 skew — handler [9.05, 9.09]
    collector.ingest_payload(_payload(
        [_row("rpc/shard_x", 11, 2, 1, 9.05, 9.09)],
        pid=200, label="replica", offset_us=2e6))
    assert collector.sweep(force=True) == 1
    (exemplar,) = collector.exemplars()
    assert exemplar["trace_id"] == 11
    assert exemplar["reasons"] == ["hedged"]
    assert not exemplar["incomplete"]
    spans = exemplar["spans"]  # sorted by rebased start
    assert [s["name"] for s in spans] == ["rpc/client/shard_x",
                                          "rpc/shard_x"]
    assert spans[0]["start"] == pytest.approx(11.0)
    assert spans[1]["start"] == pytest.approx(11.05)  # nests inside
    assert {s["pid"] for s in spans} == {100, 200}
    attr = exemplar["attribution"]
    assert attr["processes"] == 2 and attr["klass"] == "interactive"
    # handler covers 40 of the client's 100 ms: wire self-time is 60
    assert attr["segments"]["wire"] == pytest.approx(0.06, abs=1e-6)
    assert attr["segments"]["rpc_handler"] == pytest.approx(0.04,
                                                            abs=1e-6)


def test_collector_skew_folds_into_the_rebase():
    collector = TraceCollector(_registry(), linger_s=0.0, sample=1.0)
    collector.ingest_payload(_payload(
        [_row("rpc/shard_x", 5, 1, None, 1.0, 2.0)],
        offset_us=1e6, skew_us=-5e5))
    collector.sweep(force=True)
    (exemplar,) = collector.exemplars()
    assert exemplar["spans"][0]["start"] == pytest.approx(1.5)


def test_lossy_source_marks_its_traces_incomplete():
    """A batch whose cumulative ``dropped`` grew means the source lost
    spans since last time: traces it feeds from then on are surfaced
    incomplete, not presented as whole trees."""
    registry = _registry()
    collector = TraceCollector(registry, linger_s=0.0, sample=1.0)
    collector.ingest_payload(_payload(
        [_row("a", 1, 1, None, 0.0, 1.0)], dropped=0))
    collector.sweep(force=True)
    collector.ingest_payload(_payload(
        [_row("a", 2, 2, None, 0.0, 1.0)], dropped=3))
    collector.sweep(force=True)
    second, first = collector.exemplars()  # newest first
    assert not first["incomplete"]
    assert second["incomplete"]
    assert registry.counter("fleettrace/ingest/lossy_batches").value == 1
    assert registry.counter("fleettrace/traces/incomplete").value == 1
    # same cumulative count again = no NEW loss
    collector.ingest_payload(_payload(
        [_row("a", 3, 3, None, 0.0, 1.0)], dropped=3))
    collector.sweep(force=True)
    assert collector.exemplars(1)[0]["incomplete"] is False


def test_live_traces_evict_oldest_over_the_cap():
    registry = _registry()
    collector = TraceCollector(registry, max_traces=4, linger_s=3600.0,
                               sample=1.0)
    for tid in range(1, 7):
        collector.ingest_payload(_payload(
            [_row("a", tid, tid * 10, None, 0.0, 1.0)]))
    assert registry.gauge("fleettrace/traces/live").value == 4
    assert registry.counter("fleettrace/traces/evicted").value == 2
    collector.sweep(force=True)
    kept = {e["trace_id"] for e in collector.exemplars(limit=16)}
    assert kept == {3, 4, 5, 6}  # 1 and 2 were the oldest


# == tail-based retention ===================================================


def test_unmarked_traces_are_attributed_then_sampled_out():
    """sample=0: an unmarked trace contributes to the per-class tables
    (attribution is unbiased) but keeps no spans."""
    registry = _registry()
    collector = TraceCollector(registry, linger_s=0.0, sample=0.0)
    collector.ingest_payload(_payload(
        [_row("rpc/shard_x", 9, 1, None, 0.0, 0.5,
              {"klass": "bulk_audit"})]))
    collector.sweep(force=True)
    assert collector.exemplars() == []
    assert registry.counter("fleettrace/traces/sampled_out").value == 1
    tables = collector.attribution()
    assert tables["traces"]["assembled"] == 1
    row = tables["classes"]["bulk_audit"]["total"]
    assert row["count"] == 1 and row["mean_ms"] == pytest.approx(500.0)
    assert tables["segments"][-2:] == [HEDGE_WASTED, "total"]


def test_hash_sample_is_deterministic_per_trace_id():
    """sample=1.0 keeps everything; the hash decision is a pure
    function of the trace id — two collectors agree."""
    decisions = []
    for _ in range(2):
        collector = TraceCollector(_registry(), linger_s=0.0, sample=0.5)
        for tid in range(1, 33):
            # strictly decreasing durations: nothing ever ranks into
            # the top quantile, so retention is the hash sample alone
            collector.ingest_payload(_payload(
                [_row("a", tid, tid, None, 0.0, (33 - tid) * 1e-3)]))
        collector.sweep(force=True)
        decisions.append(sorted(e["trace_id"]
                                for e in collector.exemplars(limit=64)))
    assert decisions[0] == decisions[1]
    assert 0 < len(decisions[0]) < 32  # a sample, not all-or-nothing
    for exemplar in collector.exemplars(limit=64):
        assert exemplar["reasons"] == ["sampled"]


def test_top_quantile_traces_are_retained_once_history_accumulates():
    collector = TraceCollector(_registry(), linger_s=0.0, sample=0.0,
                               quantile=0.99)
    for tid in range(1, 17):  # build ranking history: 1..16 ms
        collector.ingest_payload(_payload(
            [_row("a", tid, tid, None, 0.0, tid * 1e-3)]))
        collector.sweep(force=True)
    assert collector.exemplars() == []  # not enough history yet
    collector.ingest_payload(_payload(
        [_row("a", 99, 990, None, 0.0, 0.1)]))  # 100 ms outlier
    collector.sweep(force=True)
    (exemplar,) = collector.exemplars()
    assert exemplar["trace_id"] == 99
    assert exemplar["reasons"] == ["tail_quantile"]


def test_breach_hook_retains_the_breached_class():
    """An SLO breach onset keeps every LIVE trace of the breached
    class and opens a window that catches the ones still in flight."""
    collector = TraceCollector(_registry(), linger_s=3600.0, sample=0.0,
                               breach_window_s=60.0)
    collector.ingest_payload(_payload(
        [_row("a", 1, 1, None, 0.0, 1.0, {"klass": "interactive"})]))
    collector.ingest_payload(_payload(
        [_row("a", 2, 2, None, 0.0, 1.0, {"klass": "bulk_audit"})]))
    collector.on_breach("interactive", 20.0, 8.0)
    collector.sweep(force=True)
    kept = {e["trace_id"]: e for e in collector.exemplars(limit=16)}
    assert set(kept) == {1}
    assert kept[1]["reasons"] == ["slo_breach", "slo_breach_window"]
    # the window keeps catching interactive traces finalized later
    collector.ingest_payload(_payload(
        [_row("a", 3, 3, None, 0.0, 1.0, {"klass": "interactive"})]))
    collector.sweep(force=True)
    assert collector.exemplars(1)[0]["reasons"] == ["slo_breach_window"]


def test_recorder_event_opens_a_global_retention_window():
    collector = TraceCollector(_registry(), linger_s=0.0, sample=0.0,
                               breach_window_s=60.0)
    collector.on_recorder_event("heartbeat")  # not a fatal kind
    collector.ingest_payload(_payload(
        [_row("a", 1, 1, None, 0.0, 1.0)]))
    collector.sweep(force=True)
    assert collector.exemplars() == []
    collector.on_recorder_event("breaker_trip")
    collector.ingest_payload(_payload(
        [_row("a", 2, 2, None, 0.0, 1.0)]))
    collector.sweep(force=True)
    assert collector.exemplars(1)[0]["reasons"] == ["event_window"]


# == critical-path attribution ==============================================


def test_segment_vocabulary_covers_the_instrumented_span_names():
    assert segment_for("serving/ecrecover/queue_wait") == "queue_wait"
    assert segment_for("serving/ecrecover/batch_assembly") == \
        "batch_assembly"
    assert segment_for("serving/ecrecover/device_dispatch") == \
        "device_dispatch"
    assert segment_for("serving/ecrecover/future_wake") == "future_wake"
    assert segment_for("rpc/client/shard_ecrecover") == "wire"
    assert segment_for("rpc/shard_ecrecover") == "rpc_handler"
    assert segment_for("fleet/route") == "frontend_route"
    assert segment_for("fleet/attempt") == "frontend_route"
    assert segment_for("fleet/hedge_wasted") == HEDGE_WASTED
    assert segment_for("notary/audit") == "actor_queue"
    assert segment_for("bench/fleettrace_request") == "other"
    assert all(segment_for(f"x/{s}") in SEGMENTS for s in ("y",))


def test_self_times_telescope_to_the_root_duration():
    """The sum identity on a synthetic 3-process fleet tree: every
    segment's self-time, summed, equals the root span's duration —
    with the hedge-wasted duplicate reported OUTSIDE the identity."""
    spans = [
        # bench client span: the whole request, 100 ms
        {"name": "rpc/client/shard_x", "trace": 1, "span": 1,
         "parent": None, "start": 0.0, "end": 0.100, "tags": {},
         "pid": 1},
        # frontend handler covers 90 of it
        {"name": "rpc/shard_x", "trace": 1, "span": 2, "parent": 1,
         "start": 0.005, "end": 0.095, "tags": {}, "pid": 2},
        {"name": "fleet/route", "trace": 1, "span": 3, "parent": 2,
         "start": 0.010, "end": 0.090,
         "tags": {"klass": "interactive"}, "pid": 2},
        {"name": "fleet/attempt", "trace": 1, "span": 4, "parent": 3,
         "start": 0.012, "end": 0.088, "tags": {}, "pid": 2},
        # frontend -> replica wire
        {"name": "rpc/client/shard_x", "trace": 1, "span": 5,
         "parent": 4, "start": 0.014, "end": 0.086, "tags": {},
         "pid": 2},
        # replica handler + serving pipeline
        {"name": "rpc/shard_x", "trace": 1, "span": 6, "parent": 5,
         "start": 0.020, "end": 0.080, "tags": {}, "pid": 3},
        {"name": "serving/ecrecover/request", "trace": 1, "span": 7,
         "parent": 6, "start": 0.022, "end": 0.078, "tags": {},
         "pid": 3},
        {"name": "serving/ecrecover/queue_wait", "trace": 1, "span": 8,
         "parent": 7, "start": 0.022, "end": 0.030, "tags": {},
         "pid": 3},
        {"name": "serving/ecrecover/batch_assembly", "trace": 1,
         "span": 9, "parent": 7, "start": 0.030, "end": 0.040,
         "tags": {}, "pid": 3},
        {"name": "serving/ecrecover/device_dispatch", "trace": 1,
         "span": 10, "parent": 7, "start": 0.040, "end": 0.070,
         "tags": {}, "pid": 3},
        # concurrent duplicate the hedge threw away: NOT wall time
        {"name": "fleet/hedge_wasted", "trace": 1, "span": 11,
         "parent": 3, "start": 0.012, "end": 0.085,
         "tags": {"replica": "r0", "winner": "r1"}, "pid": 2},
    ]
    attr = attribute(spans)
    assert attr["root"] == "rpc/client/shard_x"
    assert attr["klass"] == "interactive"
    assert attr["processes"] == 3
    assert attr["spans"] == 11 and attr["orphan_spans"] == 0
    assert attr["total_s"] == pytest.approx(0.100)
    assert sum(attr["segments"].values()) == pytest.approx(0.100)
    assert attr["hedge_wasted_s"] == pytest.approx(0.073)
    segments = attr["segments"]
    assert segments["wire"] == pytest.approx(0.010 + 0.012)
    assert segments["queue_wait"] == pytest.approx(0.008)
    assert segments["batch_assembly"] == pytest.approx(0.010)
    assert segments["device_dispatch"] == pytest.approx(0.030)
    assert segments["frontend_route"] == pytest.approx(0.008)


def test_orphan_subtrees_are_surfaced_not_grafted():
    """A span whose parent never arrived (lossy source) must not be
    silently attached to the widest root — it is counted orphaned."""
    spans = [
        {"name": "rpc/client/shard_x", "trace": 1, "span": 1,
         "parent": None, "start": 0.0, "end": 0.1, "tags": {}},
        {"name": "serving/x/device_dispatch", "trace": 1, "span": 9,
         "parent": 777, "start": 0.02, "end": 0.04, "tags": {}},
    ]
    attr = attribute(spans)
    assert attr["root"] == "rpc/client/shard_x"
    assert attr["orphan_spans"] == 1
    assert attr["segments"]["device_dispatch"] == 0.0
    assert attribute([]) is None


def test_skewed_child_cannot_drive_negative_self_time():
    spans = [
        {"name": "rpc/client/x", "trace": 1, "span": 1, "parent": None,
         "start": 0.0, "end": 0.010, "tags": {}},
        # cross-clock skew: the child overhangs its parent both ways
        {"name": "rpc/x", "trace": 1, "span": 2, "parent": 1,
         "start": -0.005, "end": 0.020, "tags": {}},
    ]
    attr = attribute(spans)
    assert attr["segments"]["wire"] == 0.0  # clipped, not negative
    assert all(v >= 0.0 for v in attr["segments"].values())


# == the wire envelope ======================================================


def test_rpc_response_envelope_links_client_span_to_handler_span():
    """`traceCtx` on the response names the handler's exact span: the
    caller's client span joins one trace with the remote handler and
    tags the remote span id (unambiguous under retries/hedges)."""
    from gethsharding_tpu.rpc.client import RPCClient
    from gethsharding_tpu.rpc.server import RPCServer
    from gethsharding_tpu.smc.chain import SimulatedMainchain

    tracing.enable(ring_spans=4096)
    tracing.TRACER.clear()
    server = RPCServer(SimulatedMainchain())
    server.start()
    client = RPCClient(*server.address)
    try:
        booked = metrics.timer("rpc/blockNumber/server_time")
        count = booked.count
        client.call("shard_blockNumber")
        # the server closes its enclosing span after it has flushed
        deadline = time.monotonic() + 10.0
        while booked.count == count and time.monotonic() < deadline:
            time.sleep(0.001)
        spans = tracing.TRACER.recent_spans()
        handler = next(s for s in spans
                       if s["name"] == "rpc/shard_blockNumber")
        client_span = next(s for s in spans
                           if s["name"] == "rpc/client/shard_blockNumber")
        # the server adopted the caller's trace and parented under it:
        # handler < server_time < the client's roundtrip < its call
        assert handler["trace"] == client_span["trace"]
        by_id = {s["span"]: s for s in spans}
        chain = [handler]
        while chain[-1]["parent"] in by_id:
            chain.append(by_id[chain[-1]["parent"]])
        assert [s["name"] for s in chain[1:]] == [
            "rpc/blockNumber/server_time", "rpc/client/roundtrip",
            "rpc/client/shard_blockNumber"]
        # ... and the response envelope told the caller which span
        assert client_span["tags"]["remote_trace"] == handler["trace"]
        assert client_span["tags"]["remote_span"] == handler["span"]
    finally:
        client.close()
        server.stop()
        tracing.TRACER.clear()
        tracing.disable()


def test_trace_export_rpc_requires_a_collector():
    from gethsharding_tpu.rpc.client import RPCClient
    from gethsharding_tpu.rpc.server import RPCServer
    from gethsharding_tpu.smc.chain import SimulatedMainchain

    server = RPCServer(SimulatedMainchain())
    server.start()
    client = RPCClient(*server.address)
    try:
        ack = client.call("shard_traceExport",
                          _payload([_row("a", 1, 1, None, 0.0, 1.0)]))
        assert ack == {"accepted": False, "spans": 0}
        assert client.call("shard_traceAttribution") is None
        assert client.call("shard_traceExemplars", 4) == []
        handshake = client.call("shard_traceHandshake")
        assert handshake["pid"] > 0 and handshake["wall_us"] > 0
    finally:
        client.close()
        server.stop()


# == boot shapes ============================================================


def test_boot_collector_assembles_own_spans_end_to_end(monkeypatch):
    """Single-process shape: boot_collector's in-proc exporter feeds
    the collector from this process's tracer; a finished span tree
    shows up in attribution + exemplars + status; shutdown unwinds."""
    from gethsharding_tpu import fleettrace

    monkeypatch.setenv("GETHSHARDING_FLEETTRACE_SAMPLE", "1.0")
    registry = _registry()
    collector = fleettrace.boot_collector(registry, start_sweep=False)
    try:
        assert fleettrace.active() is collector
        assert fleettrace.boot_collector(registry) is collector  # idem
        with tracing.span("rpc/shard_demo", klass="interactive"):
            with tracing.span("serving/demo/device_dispatch"):
                pass
        fleettrace.EXPORTER.flush()
        collector.sweep(force=True)
        status = fleettrace.fleettrace_status()
        assert status["active"] and status["assembled"] >= 1
        assert status["export"]["spans"] >= 2
        tables = collector.attribution()
        assert "interactive" in tables["classes"]
        exemplar = collector.exemplars(1)[0]
        assert {s["name"] for s in exemplar["spans"]} == {
            "rpc/shard_demo", "serving/demo/device_dispatch"}
        assert exemplar["spans"][0]["pid"] is not None
    finally:
        fleettrace.shutdown()
        tracing.TRACER.clear()
        tracing.disable()
    assert fleettrace.active() is None
    assert fleettrace.EXPORTER is None
    assert fleettrace.fleettrace_status() == {"active": False}
