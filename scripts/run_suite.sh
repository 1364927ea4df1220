#!/bin/bash
# Maximally isolated full-suite run: one short-lived pytest process per
# test file, each with the persistent compile cache enabled.
#
# Since r3 a plain one-process `pytest tests/` is ALSO green (conftest
# bounds XLA:CPU's executable-count pressure with jax.clear_caches()
# per module — the root cause of the old segfault); this script remains
# as the fully isolated equivalent (one crash cannot take out the whole
# run). Coverage is identical; a failing file fails the script.
set -u
cd "$(dirname "$0")/.."
fail=0

# -- observability smoke: boot an observer node, scrape every surface ------
# A real `tpu-sharding sharding` process must answer /healthz, Prometheus
# /metrics?format=prom and /trace with 200 + non-empty payloads — the
# curl-level contract the dashboards/scrapers depend on, checked against
# a live process rather than an in-process test double.
obs_port=$(python -c "import socket; s = socket.socket(); \
s.bind(('127.0.0.1', 0)); print(s.getsockname()[1]); s.close()")
echo "== observability smoke (http://127.0.0.1:$obs_port)"
JAX_PLATFORMS=cpu python -m gethsharding_tpu.node.cli sharding \
    --actor observer --http "$obs_port" --trace --fleettrace --runtime 60 \
    --blocktime 0.2 --txinterval 1.0 --verbosity error &
obs_pid=$!
up=0
for _ in $(seq 1 100); do
    if curl -sf "http://127.0.0.1:$obs_port/healthz" >/dev/null 2>&1; then
        up=1; break
    fi
    sleep 0.2
done
if [ "$up" = 1 ]; then
    for ep in "/healthz" "/metrics?format=prom" "/trace"; do
        body=$(curl -sf "http://127.0.0.1:$obs_port$ep") || body=""
        if [ -z "$body" ]; then
            echo "observability smoke FAILED: $ep returned non-200 or empty"
            fail=1
        fi
    done
    # the SLO plane boots with the node: its burn-rate gauges must be
    # present on the Prometheus exposition from the first scrape
    prom=$(curl -sf "http://127.0.0.1:$obs_port/metrics?format=prom") || prom=""
    if ! echo "$prom" | grep -q "gethsharding_slo_interactive_burn_rate"; then
        echo "observability smoke FAILED: slo/interactive/burn_rate missing" \
             "from /metrics?format=prom"
        fail=1
    fi
    # ... and so must the perfwatch trust counters (timer self-check +
    # flight recorder), registered at package import
    if ! echo "$prom" | grep -q "gethsharding_perfwatch_timer_suspect_total"
    then
        echo "observability smoke FAILED: perfwatch/timer_suspect missing" \
             "from /metrics?format=prom"
        fail=1
    fi
    # ... and the fleettrace collector booted by --fleettrace: its
    # ingest counters must reach the exposition from the first scrape
    if ! echo "$prom" | grep -q "gethsharding_fleettrace_ingest_spans_total"
    then
        echo "observability smoke FAILED: fleettrace/ingest/spans missing" \
             "from /metrics?format=prom"
        fail=1
    fi
    # the /status perf section renders (last ledger record + gate +
    # recorder state)
    if ! curl -sf "http://127.0.0.1:$obs_port/status" \
            | grep -q '"perf"'; then
        echo "observability smoke FAILED: /status has no perf section"
        fail=1
    fi
    # ... and so does the fleettrace section, live (active collector)
    if ! curl -sf "http://127.0.0.1:$obs_port/status" | python -c "
import json, sys
status = json.load(sys.stdin)
assert status['fleettrace']['active'], status.get('fleettrace')
"; then
        echo "observability smoke FAILED: /status fleettrace section" \
             "missing or inactive under --fleettrace"
        fail=1
    fi
else
    echo "observability smoke FAILED: node never answered /healthz"
    fail=1
fi
kill "$obs_pid" 2>/dev/null
wait "$obs_pid" 2>/dev/null

# -- resident/overlap parity smoke: the device-resident pk cache and the
# async committee path, exercised end-to-end on hermetic CPU at a small
# shape — warm dispatch must ship zero G2 bytes, async == sync == scalar
echo "== resident/overlap smoke"
# pin the knob under test: an ambient GETHSHARDING_TPU_RESIDENT=0 A/B
# setting must not fail the suite's zero-G2 assertion
JAX_PLATFORMS=cpu GETHSHARDING_TPU_RESIDENT=1 python - <<'PYEOF' || fail=1
from gethsharding_tpu.crypto import bn256 as bls
from gethsharding_tpu.sigbackend import get_backend

py, jx = get_backend("python"), get_backend("jax")
msgs, sig_rows, pk_rows, keys = [], [], [], []
for i in range(3):
    tag = b"suite-%d" % i
    ks = [bls.bls_keygen(tag + bytes([j])) for j in range(2)]
    sigs = [bls.bls_sign(tag, sk) for sk, _ in ks]
    if i == 1:
        sigs[0] = bls.bls_sign(b"tampered", ks[0][0])
    msgs.append(tag); sig_rows.append(sigs)
    pk_rows.append([pk for _, pk in ks]); keys.append(("suite", i))
want = py.bls_verify_committees(msgs, sig_rows, pk_rows)
assert jx.bls_verify_committees(
    msgs, sig_rows, pk_rows, pk_row_keys=keys) == want
fut = jx.bls_verify_committees_async(
    msgs, sig_rows, pk_rows, pk_row_keys=keys)
assert fut.result() == want
assert jx.last_wire["g2_wire_bytes"] == 0, jx.last_wire  # warm = resident
print("resident/overlap smoke OK:", jx.last_wire)
PYEOF

# -- precomp smoke: fixed-base line tables end-to-end on hermetic CPU —
# ONE audit with precomp on vs off, verdicts bit-identical to the
# scalar reference (incl. a forged row), the warm dispatch ships zero
# G2 bytes AND runs from the cached line tables (precomp wire stamp),
# and the flag-off backend takes today's recompute path unchanged
echo "== precomp smoke"
JAX_PLATFORMS=cpu GETHSHARDING_TPU_RESIDENT=1 GETHSHARDING_PRECOMP=1 \
python - <<'PYEOF' || fail=1
import os

from gethsharding_tpu.crypto import bn256 as bls
from gethsharding_tpu.sigbackend import PythonSigBackend
from gethsharding_tpu.sigbackend.dispatch import JaxSigBackend

py = PythonSigBackend()
msgs, sig_rows, pk_rows, keys = [], [], [], []
for i in range(3):
    tag = b"pre-suite-%d" % i
    ks = [bls.bls_keygen(tag + bytes([j])) for j in range(2)]
    sigs = [bls.bls_sign(tag, sk) for sk, _ in ks]
    if i == 1:
        sigs[0] = bls.bls_sign(b"tampered", ks[0][0])
    msgs.append(tag); sig_rows.append(sigs)
    pk_rows.append([pk for _, pk in ks]); keys.append(("pre-suite", i))
want = py.bls_verify_committees(msgs, sig_rows, pk_rows)
on = JaxSigBackend()
assert on._precomp, "GETHSHARDING_PRECOMP=1 did not engage"
cold = on.bls_verify_committees(msgs, sig_rows, pk_rows, pk_row_keys=keys)
warm = on.bls_verify_committees(msgs, sig_rows, pk_rows, pk_row_keys=keys)
assert cold == warm == want, (cold, warm, want)
assert on.last_wire["precomp"] is True, on.last_wire
assert on.last_wire["g2_wire_bytes"] == 0, on.last_wire  # warm line tables
os.environ["GETHSHARDING_PRECOMP"] = "0"
off = JaxSigBackend()
assert not off._precomp
assert off.bls_verify_committees(
    msgs, sig_rows, pk_rows, pk_row_keys=keys) == want
assert off.last_wire["precomp"] is False, off.last_wire
print("precomp smoke OK:", on.last_wire)
PYEOF

# -- DAS smoke: the das counters must reach the Prometheus exposition
# (the sampled vote end to end is tests/test_das.py's)
echo "== DAS smoke"
JAX_PLATFORMS=cpu python - <<'PYEOF' || fail=1
from gethsharding_tpu import metrics
from gethsharding_tpu.metrics import prometheus_text

metrics.counter("das/samples_verified").inc(3)
metrics.counter("das/sample_failures").inc(0)
text = prometheus_text()
for needle in ("gethsharding_das_samples_verified_total",
               "gethsharding_das_sample_failures_total"):
    assert needle in text, needle
print("DAS prometheus exposition OK")
PYEOF

# -- das-poly smoke: polynomial-multiproof DAS end-to-end on hermetic
# CPU — a sampled notary under --da-proofs=poly must vote with ZERO
# body fetches, every sampled set arriving under ONE constant-size
# multiproof; then a corrupt-multiproof chaos run must trip the
# breaker through the soundness spot-checker while the verdict stays
# correct on the scalar fallback
echo "== das-poly smoke"
JAX_PLATFORMS=cpu python - <<'PYEOF' || fail=1
import random

from gethsharding_tpu.actors.notary import Notary
from gethsharding_tpu.actors.proposer import create_collation
from gethsharding_tpu.core.shard import Shard
from gethsharding_tpu.core.types import Transaction
from gethsharding_tpu.das.service import DASService
from gethsharding_tpu.db.kv import MemoryKV
from gethsharding_tpu.mainchain.client import SMCClient
from gethsharding_tpu.p2p.messages import CollationBodyRequest
from gethsharding_tpu.p2p.service import Hub, P2PServer
from gethsharding_tpu.params import Config, ETHER
from gethsharding_tpu.sigbackend import get_backend
from gethsharding_tpu.smc.chain import SimulatedMainchain

config = Config(quorum_size=1, period_length=4)
chain = SimulatedMainchain(config=config)
prop_client = SMCClient(backend=chain, config=config)
not_client = SMCClient(backend=chain, config=config)
chain.fund(prop_client.account(), 2000 * ETHER)
chain.fund(not_client.account(), 2000 * ETHER)
hub = Hub()
watch = P2PServer(hub)
watch.start()
body_watch = watch.subscribe(CollationBodyRequest)
svc_prop = DASService(client=prop_client, p2p=P2PServer(hub), samples=4,
                      proof_mode="poly", fetch_timeout=4.0)
svc_not = DASService(client=not_client, p2p=P2PServer(hub), samples=4,
                     proof_mode="poly", fetch_timeout=4.0)
svc_prop.start()
svc_not.start()
notary = Notary(client=not_client, shard=Shard(0, MemoryKV()),
                p2p=svc_not.p2p, config=config, deposit_flag=True,
                all_shards=False, sig_backend=get_backend("python"),
                das=svc_not, da_mode="sampled")
notary.start()
chain.fast_forward(1)
rng = random.Random(5)
periods = 2
try:
    for _ in range(periods):
        period = chain.current_period()
        collation = create_collation(
            prop_client, 0, period,
            [Transaction(nonce=period,
                         payload=bytes(rng.randrange(256)
                                       for _ in range(20000)))])
        svc_prop.publish(0, period, collation.header.chunk_root,
                         collation.body)
        prop_client.add_header(0, period, collation.header.chunk_root,
                               collation.header.proposer_signature)
        chain.commit()
        notary.notarize_collations(head=chain.block_number)
        while chain.current_period() == period:
            chain.commit()
    assert notary.votes_submitted == periods, notary.errors
    assert body_watch.try_get() is None, \
        "a CollationBodyRequest left the poly-sampled notary"
    assert svc_not.m_multiproofs_fetched.value >= periods
finally:
    notary.stop()
    svc_prop.stop()
    svc_not.stop()
    watch.stop()
print("das-poly e2e OK:", periods, "poly-sampled votes, zero body fetches")
PYEOF
JAX_PLATFORMS=cpu python - <<'PYEOF' || fail=1
import random

from gethsharding_tpu.das import pcs
from gethsharding_tpu.metrics import DEFAULT_REGISTRY
from gethsharding_tpu.resilience.breaker import (OPEN, CircuitBreaker,
                                                 FailoverSigBackend)
from gethsharding_tpu.resilience.chaos import ChaosSigBackend, parse_spec
from gethsharding_tpu.resilience.soundness import SpotCheckSigBackend
from gethsharding_tpu.sigbackend import PythonSigBackend

rng = random.Random(9)
values = [rng.randrange(pcs.N) for _ in range(8)]
proof, evals = pcs.open_multi(values, (1, 5))
cols = ([pcs.g1_to_bytes(pcs.commit(values))], [[1, 5]], [evals],
        [pcs.g1_to_bytes(proof)], [8])
schedule = parse_spec("seed=7,backend.das_verify_multiproofs:mode=corrupt")
breaker = CircuitBreaker(name="das-poly", fault_threshold=1, reset_s=60.0)
backend = FailoverSigBackend(
    SpotCheckSigBackend(ChaosSigBackend(PythonSigBackend(), schedule),
                        rate=1.0, rows=1),
    PythonSigBackend(), breaker=breaker)
got = backend.das_verify_multiproofs(*[list(c) for c in cols])
assert got == [True], got  # detected -> served correct from the fallback
assert breaker.state == OPEN, breaker.state_name
assert DEFAULT_REGISTRY.counter(
    "resilience/soundness/das_verify_multiproofs/mismatches").value >= 1
print("das-poly chaos OK: corrupt multiproof verdict tripped the"
      " breaker, verdict stayed correct")
PYEOF

# -- chaos/failover smoke: a devnet-style notary rides a seeded failure
# schedule end-to-end — injected device faults mid-audit must trip the
# breaker, every period's votes must land on the scalar fallback, the
# breaker must re-close through a matching differential probe, and the
# breaker counters must appear in the Prometheus exposition
echo "== chaos failover smoke"
JAX_PLATFORMS=cpu python - <<'PYEOF' || fail=1
import time

from gethsharding_tpu.actors.notary import Notary
from gethsharding_tpu.actors.proposer import create_collation
from gethsharding_tpu.core.shard import Shard
from gethsharding_tpu.core.types import Transaction
from gethsharding_tpu.db.kv import MemoryKV
from gethsharding_tpu.mainchain.client import SMCClient
from gethsharding_tpu.metrics import prometheus_text
from gethsharding_tpu.params import Config, ETHER
from gethsharding_tpu.resilience.breaker import (
    CLOSED, CircuitBreaker, FailoverSigBackend)
from gethsharding_tpu.resilience.chaos import (ChaosSchedule,
                                               ChaosSigBackend, parse_spec)
from gethsharding_tpu.sigbackend import PythonSigBackend
from gethsharding_tpu.smc.chain import SimulatedMainchain

config = Config(quorum_size=1, period_length=4)
backend = SimulatedMainchain(config=config)
client = SMCClient(backend=backend, config=config)
backend.fund(client.account(), 2000 * ETHER)
schedule = parse_spec("seed=7,backend.bls_verify_committees=2")
breaker = CircuitBreaker(name="sigbackend", fault_threshold=1,
                         reset_s=0.005)
failover = FailoverSigBackend(
    ChaosSigBackend(PythonSigBackend(), schedule),
    PythonSigBackend(), breaker=breaker)
notary = Notary(client=client, shard=Shard(0, MemoryKV()), config=config,
                deposit_flag=True, all_shards=False, sig_backend=failover)
notary.start()
backend.fast_forward(1)
periods = []
for _ in range(5):
    period = backend.current_period()
    collation = create_collation(
        client, 0, period, [Transaction(nonce=period, payload=b"c")])
    notary.shard.save_collation(collation)
    client.add_header(0, period, collation.header.chunk_root,
                      collation.header.proposer_signature)
    while backend.current_period() == period:
        backend.commit()
    periods.append(period)
    time.sleep(0.01)
notary.stop()
assert notary.votes_submitted == len(periods), notary.errors
assert backend.last_approved_collation(0) == periods[-1]  # on fallback
assert schedule.injected.get("backend.bls_verify_committees") == 2
assert breaker.state == CLOSED, breaker.state_name  # probed + re-closed
prom = prometheus_text()
for needle in ("gethsharding_resilience_breaker_sigbackend_trips_total",
               "gethsharding_resilience_breaker_sigbackend_closes_total",
               "gethsharding_resilience_breaker_sigbackend_state"):
    assert needle in prom, needle
print("chaos failover smoke OK: periods", periods,
      "injected", schedule.injected)
PYEOF

# -- soundness smoke: silent corruption (chaos mode=corrupt — wrong
# answers, NO exception from the device path) must trip the breaker
# through the spot-checker, every answer must still come back correct
# from the scalar fallback, and the soundness counters must reach the
# Prometheus exposition
echo "== soundness smoke"
JAX_PLATFORMS=cpu python - <<'PYEOF' || fail=1
from gethsharding_tpu.metrics import DEFAULT_REGISTRY, prometheus_text
from gethsharding_tpu.resilience.breaker import (
    OPEN, CircuitBreaker, FailoverSigBackend)
from gethsharding_tpu.resilience.chaos import ChaosSigBackend, parse_spec
from gethsharding_tpu.resilience.soundness import SpotCheckSigBackend
from gethsharding_tpu.sigbackend import PythonSigBackend

schedule = parse_spec("seed=7,backend.ecrecover_addresses:mode=corrupt")
breaker = CircuitBreaker(name="soundness", fault_threshold=1,
                         reset_s=60.0)
backend = FailoverSigBackend(
    SpotCheckSigBackend(ChaosSigBackend(PythonSigBackend(), schedule),
                        rate=1.0),
    PythonSigBackend(), breaker=breaker)
digests, sigs = [b"\x11" * 32] * 4, [b"\x22" * 65] * 4
want = PythonSigBackend().ecrecover_addresses(digests, sigs)
got = backend.ecrecover_addresses(digests, sigs)
assert got == want, got  # detected -> served correct from the fallback
assert breaker.state == OPEN, breaker.state_name  # tripped on SILENT
assert DEFAULT_REGISTRY.counter(
    "resilience/soundness/ecrecover_addresses/mismatches").value >= 1
assert schedule.injected.get("backend.ecrecover_addresses") == 1
# ... and the counters reach the scrape surface
prom = prometheus_text()
for needle in ("gethsharding_resilience_soundness_ecrecover_addresses_"
               "checks_total",
               "gethsharding_resilience_soundness_ecrecover_addresses_"
               "mismatches_total",
               "gethsharding_resilience_breaker_soundness_trips_total"):
    assert needle in prom, needle
print("soundness smoke OK: silent corruption tripped the breaker,"
      " answers stayed correct")
PYEOF

# -- fleet router smoke: two breaker-guarded serving replicas behind the
# shard-aware router — seeded chaos trips r0's breaker, every answer
# stays correct, the router drains r0 and its refresh-side probe
# re-promotes it through the half-open differential, and the fleet
# counters reach the Prometheus exposition
echo "== fleet router smoke"
JAX_PLATFORMS=cpu python - <<'PYEOF' || fail=1
import time

from gethsharding_tpu.crypto import secp256k1 as ecdsa
from gethsharding_tpu.crypto.keccak import keccak256
from gethsharding_tpu.fleet import FleetRouter, Replica, RouterSigBackend
from gethsharding_tpu.metrics import prometheus_text
from gethsharding_tpu.resilience.breaker import (CircuitBreaker,
                                                 FailoverSigBackend)
from gethsharding_tpu.resilience.chaos import ChaosSchedule, ChaosSigBackend
from gethsharding_tpu.serving import ServingConfig, ServingSigBackend
from gethsharding_tpu.sigbackend import PythonSigBackend

schedule = ChaosSchedule(seed=7, rules={"backend.ecrecover_addresses": 3})
servings = [
    ServingSigBackend(ChaosSigBackend(PythonSigBackend(), schedule),
                      ServingConfig(flush_us=200)),
    ServingSigBackend(PythonSigBackend(), ServingConfig(flush_us=200)),
]
breaker0 = CircuitBreaker(name="smoke-r0", fault_threshold=3, reset_s=0.2)
router = FleetRouter([
    Replica("r0", FailoverSigBackend(servings[0], PythonSigBackend(),
                                     breaker=breaker0)),
    Replica("r1", FailoverSigBackend(servings[1], PythonSigBackend(),
                                     breaker=CircuitBreaker(
                                         name="smoke-r1"))),
], health_interval_s=0.0)
back = RouterSigBackend(router)
cases = []
for i in range(6):
    priv = int.from_bytes(keccak256(b"smoke-%d" % i), "big") % ecdsa.N
    digest = keccak256(b"smoke-msg-%d" % i)
    cases.append((digest, ecdsa.sign(digest, priv).to_bytes65(),
                  ecdsa.priv_to_address(priv)))
for digest, sig, want in cases[:4]:
    assert back.ecrecover_addresses([digest], [sig]) == [want]
router.refresh(force=True)
r0 = router.replicas[0]
assert r0.state == "draining", r0.state  # breaker tripped -> drained
assert schedule.injected.get("backend.ecrecover_addresses") == 3
time.sleep(0.25)
deadline = time.monotonic() + 5
while r0.state != "healthy" and time.monotonic() < deadline:
    router.refresh(force=True)
    time.sleep(0.02)
assert r0.state == "healthy", r0.state  # probe re-promoted -> re-entered
assert r0.reentries == 1
for digest, sig, want in cases[4:]:
    assert back.ecrecover_addresses([digest], [sig]) == [want]
prom = prometheus_text()
for needle in ("gethsharding_fleet_replica_r0_state",
               "gethsharding_fleet_replica_r0_routed_total",
               "gethsharding_fleet_router_calls_total",
               "gethsharding_resilience_retry_fleet_route_retries_total"):
    assert needle in prom, needle
for serving in servings:
    serving.close()
print("fleet router smoke OK: drain ->", r0.drain_events,
      "reentry ->", r0.reentries)
PYEOF

# -- fleet observability smoke: a chain_server replica + a router-side
# client in separate processes — the router's trace ships over the RPC
# trace envelope, both sides export Chrome traces, trace_merge.py folds
# them into ONE file where the stitched request's spans share a trace
# id across pid lanes; the router side's Prometheus payload carries the
# slo/<class> burn gauges and the fleet/replica federation rollups
echo "== fleet observability smoke"
obsfleet_dir=$(mktemp -d)
JAX_PLATFORMS=cpu python -m gethsharding_tpu.rpc.chain_server \
    --sigbackend python --trace \
    --trace-out "$obsfleet_dir/replica.json" --runtime 60 \
    --verbosity error > "$obsfleet_dir/server.json" &
obsfleet_pid=$!
for _ in $(seq 1 100); do
    [ -s "$obsfleet_dir/server.json" ] && break
    sleep 0.2
done
JAX_PLATFORMS=cpu OBSFLEET_DIR="$obsfleet_dir" python - <<'PYEOF' || fail=1
import json, os

from gethsharding_tpu import tracing
from gethsharding_tpu.crypto import secp256k1 as ecdsa
from gethsharding_tpu.crypto.keccak import keccak256
from gethsharding_tpu.fleet import FleetRouter, Replica, RouterSigBackend
from gethsharding_tpu.fleet.router import RpcReplicaBackend
from gethsharding_tpu.metrics import prometheus_text

out = os.environ["OBSFLEET_DIR"]
addr = json.load(open(os.path.join(out, "server.json")))
tracing.enable(ring_spans=16384)
backend = RpcReplicaBackend.dial(addr["host"], addr["port"])
router = FleetRouter([Replica("r0", backend, health=backend.health,
                              probe=None)], health_interval_s=0.0)
back = RouterSigBackend(router)
for i in range(4):
    priv = int.from_bytes(keccak256(b"obsf-%d" % i), "big") % ecdsa.N
    digest = keccak256(b"obsf-msg-%d" % i)
    got = back.ecrecover_addresses([digest],
                                   [ecdsa.sign(digest, priv).to_bytes65()])
    assert got == [ecdsa.priv_to_address(priv)], "wrong answer via router"
router.refresh(force=True)  # health + shard_metrics federation scrape
prom = prometheus_text()
for needle in ("gethsharding_slo_interactive_burn_rate",
               "gethsharding_fleet_replica_r0_serving_ecrecover_"
               "requests_count",
               "gethsharding_fleet_total_inflight"):
    assert needle in prom, needle
tracing.write_chrome_trace(os.path.join(out, "router.json"),
                           label="router")
backend.close()
print("fleet observability client OK")
PYEOF
kill -INT "$obsfleet_pid" 2>/dev/null
wait "$obsfleet_pid" 2>/dev/null
if [ -s "$obsfleet_dir/replica.json" ] && [ -s "$obsfleet_dir/router.json" ]
then
    JAX_PLATFORMS=cpu python scripts/trace_merge.py \
        "$obsfleet_dir/router.json" "$obsfleet_dir/replica.json" \
        -o "$obsfleet_dir/merged.json" >/dev/null || fail=1
    JAX_PLATFORMS=cpu OBSFLEET_DIR="$obsfleet_dir" python - <<'PYEOF' || fail=1
import json, os
from collections import defaultdict

merged = json.load(open(os.path.join(os.environ["OBSFLEET_DIR"],
                                     "merged.json")))
events = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
by_trace = defaultdict(lambda: defaultdict(set))
for e in events:
    by_trace[e["args"].get("trace_id")][e["pid"]].add(e["name"])
stitched = [t for t, pids in by_trace.items() if len(pids) >= 2]
assert stitched, "no trace id spans both processes in the merged export"
names = set()
for t in stitched:
    for pid_names in by_trace[t].values():
        names |= pid_names
assert "fleet/route" in names and "rpc/shard_ecrecover" in names, names
print("fleet observability smoke OK:", len(stitched),
      "stitched trace(s) across", len({e['pid'] for e in events}),
      "process lanes")
PYEOF
else
    echo "fleet observability smoke FAILED: missing trace exports"
    fail=1
fi
rm -rf "$obsfleet_dir"

# -- fleet frontend smoke: the REAL process topology — 2 chain_server
# replicas + 1 standalone fleet.frontend balancing them. Verdicts
# through the frontend must be bit-identical to the scalar backend
# (ecrecover AND the committee plane over the new shard_verifyCommittees
# wire), then replica r0 is KILLED mid-traffic (answers must stay
# correct via the survivor), restarted on the SAME endpoint, and must
# re-enter the rotation through the frontend's health sweep.
echo "== fleet frontend smoke (kill + restart a replica under traffic)"
ff_dir=$(mktemp -d)
ff_pa=$(python -c "import socket; s = socket.socket(); \
s.bind(('127.0.0.1', 0)); print(s.getsockname()[1]); s.close()")
ff_pb=$(python -c "import socket; s = socket.socket(); \
s.bind(('127.0.0.1', 0)); print(s.getsockname()[1]); s.close()")
JAX_PLATFORMS=cpu python -m gethsharding_tpu.rpc.chain_server \
    --sigbackend python --port "$ff_pa" --runtime 120 \
    --verbosity error > "$ff_dir/ra.json" &
ff_pid_a=$!
JAX_PLATFORMS=cpu python -m gethsharding_tpu.rpc.chain_server \
    --sigbackend python --port "$ff_pb" --runtime 120 \
    --verbosity error > "$ff_dir/rb.json" &
ff_pid_b=$!
for _ in $(seq 1 100); do
    [ -s "$ff_dir/ra.json" ] && [ -s "$ff_dir/rb.json" ] && break
    sleep 0.2
done
GETHSHARDING_PERFWATCH_DIR="$ff_dir/blackbox" JAX_PLATFORMS=cpu \
python -m gethsharding_tpu.fleet.frontend \
    --replica "127.0.0.1:$ff_pa" --replica "127.0.0.1:$ff_pb" \
    --fleet-hedge-ms 25 --health-interval 0.1 --runtime 120 \
    --verbosity error > "$ff_dir/fe.json" &
ff_pid_fe=$!
for _ in $(seq 1 100); do
    [ -s "$ff_dir/fe.json" ] && break
    sleep 0.2
done
# phase 1: verdict bit-identity through the frontend (ecrecover + the
# committee plane), against the scalar reference
JAX_PLATFORMS=cpu FF_DIR="$ff_dir" python - <<'PYEOF' || fail=1
import json, os

from gethsharding_tpu.crypto import bn256 as bls
from gethsharding_tpu.crypto import secp256k1 as ecdsa
from gethsharding_tpu.crypto.keccak import keccak256
from gethsharding_tpu.rpc import codec
from gethsharding_tpu.rpc.client import RPCClient
from gethsharding_tpu.sigbackend import PythonSigBackend

addr = json.load(open(os.path.join(os.environ["FF_DIR"], "fe.json")))
rpc = RPCClient(addr["host"], addr["port"])
py = PythonSigBackend()
for i in range(8):
    priv = int.from_bytes(keccak256(b"ffs-%d" % i), "big") % ecdsa.N
    digest = keccak256(b"ffs-msg-%d" % i)
    sig = ecdsa.sign(digest, priv).to_bytes65()
    got = rpc.call("shard_ecrecover", [codec.enc_bytes(digest)],
                   [codec.enc_bytes(sig)])
    want = py.ecrecover_addresses([digest], [sig])
    assert got == [codec.enc_bytes(bytes(want[0]))], (i, got)
msgs, sig_rows, pk_rows, keys = [], [], [], []
for i in range(3):
    tag = b"ffc-%d" % i
    ks = [bls.bls_keygen(tag + bytes([j])) for j in range(2)]
    sigs = [bls.bls_sign(tag, sk) for sk, _ in ks]
    if i == 1:
        sigs[0] = bls.bls_sign(b"tampered", ks[0][0])
    msgs.append(tag); sig_rows.append(sigs)
    pk_rows.append([pk for _, pk in ks]); keys.append(("ff", i))
want = py.bls_verify_committees(msgs, sig_rows, pk_rows)
got = rpc.call("shard_verifyCommittees",
               [codec.enc_bytes(m) for m in msgs],
               codec.enc_g1_rows(sig_rows), codec.enc_g2_rows(pk_rows),
               codec.enc_pk_row_keys(keys))
assert got == want, (got, want)
rpc.close()
print("fleet frontend phase 1 OK: ecrecover + committee plane"
      " bit-identical to scalar")
PYEOF
# phase 2: kill replica A under traffic — every answer must keep coming
# (routed to the survivor), and the frontend must mark r0 unhealthy
kill -9 "$ff_pid_a" 2>/dev/null
JAX_PLATFORMS=cpu FF_DIR="$ff_dir" python - <<'PYEOF' || fail=1
import json, os, time

from gethsharding_tpu.crypto import secp256k1 as ecdsa
from gethsharding_tpu.crypto.keccak import keccak256
from gethsharding_tpu.rpc import codec
from gethsharding_tpu.rpc.client import RPCClient

addr = json.load(open(os.path.join(os.environ["FF_DIR"], "fe.json")))
rpc = RPCClient(addr["host"], addr["port"])
for i in range(12):
    priv = int.from_bytes(keccak256(b"ffk-%d" % i), "big") % ecdsa.N
    digest = keccak256(b"ffk-msg-%d" % i)
    sig = ecdsa.sign(digest, priv).to_bytes65()
    got = rpc.call("shard_ecrecover", [codec.enc_bytes(digest)],
                   [codec.enc_bytes(sig)])
    assert got == [codec.enc_bytes(ecdsa.priv_to_address(priv))], (i, got)
    time.sleep(0.05)
deadline = time.monotonic() + 10
state = None
while time.monotonic() < deadline:
    state = rpc.call("shard_fleetStatus")["replicas"]["r0"]["state"]
    if state != "healthy":
        break
    time.sleep(0.1)
assert state != "healthy", f"frontend never noticed the kill: {state}"
rpc.close()
print("fleet frontend phase 2 OK: replica killed, answers stayed"
      " correct, r0 ->", state)
PYEOF
# phase 3: restart replica A on the SAME endpoint; the frontend's
# health sweep must re-enter it, and traffic must stay correct
JAX_PLATFORMS=cpu python -m gethsharding_tpu.rpc.chain_server \
    --sigbackend python --port "$ff_pa" --runtime 60 \
    --verbosity error > "$ff_dir/ra2.json" &
ff_pid_a2=$!
JAX_PLATFORMS=cpu FF_DIR="$ff_dir" python - <<'PYEOF' || fail=1
import json, os, time

from gethsharding_tpu.crypto import secp256k1 as ecdsa
from gethsharding_tpu.crypto.keccak import keccak256
from gethsharding_tpu.rpc import codec
from gethsharding_tpu.rpc.client import RPCClient

addr = json.load(open(os.path.join(os.environ["FF_DIR"], "fe.json")))
rpc = RPCClient(addr["host"], addr["port"])
deadline = time.monotonic() + 20
status = None
while time.monotonic() < deadline:
    status = rpc.call("shard_fleetStatus")["replicas"]["r0"]
    if status["state"] == "healthy":
        break
    time.sleep(0.2)
assert status and status["state"] == "healthy", \
    f"killed replica never re-entered after restart: {status}"
assert status["reentries"] >= 1, status
for i in range(6):
    priv = int.from_bytes(keccak256(b"ffr-%d" % i), "big") % ecdsa.N
    digest = keccak256(b"ffr-msg-%d" % i)
    sig = ecdsa.sign(digest, priv).to_bytes65()
    got = rpc.call("shard_ecrecover", [codec.enc_bytes(digest)],
                   [codec.enc_bytes(sig)])
    assert got == [codec.enc_bytes(ecdsa.priv_to_address(priv))], (i, got)
rpc.close()
print("fleet frontend smoke OK: killed replica re-entered after",
      status["reentries"], "re-entries; verdicts stayed bit-identical")
PYEOF
kill "$ff_pid_fe" "$ff_pid_b" "$ff_pid_a2" 2>/dev/null
wait "$ff_pid_fe" "$ff_pid_b" "$ff_pid_a2" 2>/dev/null
rm -rf "$ff_dir"

# -- fleettrace smoke: cross-process trace assembly on the REAL process
# topology — 2 chain_server replicas ship spans to a fleet frontend
# collector over shard_traceExport, this client exports its own spans
# the same way, and ONE interactive shard_verifyAggregates must come
# back as ONE assembled trace whose spans carry >= 3 distinct pids
# (client + frontend + replica), with the interactive class present in
# the critical-path attribution tables
echo "== fleettrace smoke (one request -> one trace across 3 processes)"
ft_dir=$(mktemp -d)
ft_fe=$(python -c "import socket; s = socket.socket(); \
s.bind(('127.0.0.1', 0)); print(s.getsockname()[1]); s.close()")
# replicas first: their export sink absorbs + retries until the
# frontend (their collector) binds the reserved port
JAX_PLATFORMS=cpu GETHSHARDING_FLEETTRACE_INTERVAL_MS=50 \
python -m gethsharding_tpu.rpc.chain_server \
    --sigbackend python --fleettrace-export "127.0.0.1:$ft_fe" \
    --runtime 120 --verbosity error > "$ft_dir/ra.json" &
ft_pid_a=$!
JAX_PLATFORMS=cpu GETHSHARDING_FLEETTRACE_INTERVAL_MS=50 \
python -m gethsharding_tpu.rpc.chain_server \
    --sigbackend python --fleettrace-export "127.0.0.1:$ft_fe" \
    --runtime 120 --verbosity error > "$ft_dir/rb.json" &
ft_pid_b=$!
for _ in $(seq 1 100); do
    [ -s "$ft_dir/ra.json" ] && [ -s "$ft_dir/rb.json" ] && break
    sleep 0.2
done
ft_ra=$(python -c "import json; a = json.load(open('$ft_dir/ra.json')); \
print('%s:%s' % (a['host'], a['port']))")
ft_rb=$(python -c "import json; a = json.load(open('$ft_dir/rb.json')); \
print('%s:%s' % (a['host'], a['port']))")
JAX_PLATFORMS=cpu GETHSHARDING_FLEETTRACE_INTERVAL_MS=50 \
GETHSHARDING_FLEETTRACE_SAMPLE=1.0 GETHSHARDING_FLEETTRACE_LINGER_S=0.4 \
python -m gethsharding_tpu.fleet.frontend \
    --port "$ft_fe" --fleettrace --replica "$ft_ra" --replica "$ft_rb" \
    --runtime 120 --verbosity error > "$ft_dir/fe.json" &
ft_pid_fe=$!
for _ in $(seq 1 100); do
    [ -s "$ft_dir/fe.json" ] && break
    sleep 0.2
done
JAX_PLATFORMS=cpu GETHSHARDING_FLEETTRACE_INTERVAL_MS=50 \
FT_DIR="$ft_dir" python - <<'PYEOF' || fail=1
import json, os, time

from gethsharding_tpu import fleettrace, tracing
from gethsharding_tpu.crypto import bn256 as bls
from gethsharding_tpu.rpc import codec
from gethsharding_tpu.rpc.client import RPCClient

addr = json.load(open(os.path.join(os.environ["FT_DIR"], "fe.json")))
fleettrace.boot_exporter("%s:%s" % (addr["host"], addr["port"]),
                         label="smoke-client")
client = RPCClient(addr["host"], addr["port"], timeout=30.0)
header = b"fleettrace-smoke"
keys = [bls.bls_keygen(bytes([i + 1])) for i in range(2)]
agg_sig = bls.bls_aggregate_sigs(
    [bls.bls_sign(header, sk) for sk, _ in keys])
agg_pk = bls.bls_aggregate_pks([pk for _, pk in keys])
call_args = ([codec.enc_bytes(header)], [codec.enc_g1(agg_sig)],
             [codec.enc_g2(agg_pk)], "interactive")
assert client.call("shard_verifyAggregates", *call_args) == [True]
with tracing.span("smoke/fleettrace_request") as probe:
    assert client.call("shard_verifyAggregates", *call_args) == [True]
trace_id = probe.trace_id
fleettrace.EXPORTER.flush()
exemplar = None
deadline = time.monotonic() + 30.0
while time.monotonic() < deadline and exemplar is None:
    for ex in client.call("shard_traceExemplars", 32):
        if ex["trace_id"] == trace_id:
            exemplar = ex
            break
    if exemplar is None:
        time.sleep(0.2)
assert exemplar is not None, \
    "the measured request never assembled into a retained trace"
pids = {span.get("pid") for span in exemplar["spans"]} - {None}
assert len(pids) >= 3, (
    "assembled trace spans %d processes, want >= 3 "
    "(client + frontend + replica): %s" % (len(pids), sorted(pids)))
attr = client.call("shard_traceAttribution")
assert attr["classes"].get("interactive"), attr["classes"]
client.close()
fleettrace.shutdown()
print("fleettrace smoke OK: one trace,", len(exemplar["spans"]),
      "spans across", len(pids), "processes")
PYEOF
kill "$ft_pid_fe" "$ft_pid_a" "$ft_pid_b" 2>/dev/null
wait "$ft_pid_fe" "$ft_pid_a" "$ft_pid_b" 2>/dev/null
rm -rf "$ft_dir"

# -- perfwatch smoke: the CPU-quick micro suite + the noise-aware
# regression gate, closed loop — seed a FRESH ledger with clean runs,
# the gate must pass; inject a labeled 1.5x slowdown into one
# registered microbench, the gate must trip (exit 1); a clean rerun
# must pass again (the outlier cannot poison the rolling median)
echo "== perfwatch smoke (micro suite + regression gate)"
pw_tmp=$(mktemp -d)
pw_led="$pw_tmp/ledger.jsonl"
pw_ok=1
for _ in 1 2 3 4; do
    JAX_PLATFORMS=cpu GETHSHARDING_PERFWATCH_LEDGER="$pw_led" \
        python -m gethsharding_tpu.perfwatch --run --check \
        >/dev/null 2>&1 || pw_ok=0
done
if [ "$pw_ok" != 1 ]; then
    # one settle retry: a cold/loaded host can scatter the first runs
    # past the band; a REAL regression persists into the next clean run
    if JAX_PLATFORMS=cpu GETHSHARDING_PERFWATCH_LEDGER="$pw_led" \
        python -m gethsharding_tpu.perfwatch --run --check >/dev/null 2>&1
    then
        pw_ok=1
    fi
fi
if [ "$pw_ok" != 1 ]; then
    echo "perfwatch smoke FAILED: clean micro-suite runs tripped the gate"
    fail=1
fi
if JAX_PLATFORMS=cpu GETHSHARDING_PERFWATCH_LEDGER="$pw_led" \
    GETHSHARDING_PERFWATCH_INJECT="clock_spin_5ms:1.5" \
    python -m gethsharding_tpu.perfwatch --run --check >/dev/null 2>&1
then
    echo "perfwatch smoke FAILED: injected 1.5x slowdown did NOT trip" \
         "the regression gate"
    fail=1
fi
# the heal step gets the SAME settle allowance as the clean loop: the
# full-suite check includes the real workload benches, whose ~20% host
# drift can organically brush the band — a REAL regression persists
# into a second clean run, a load blip does not
if ! JAX_PLATFORMS=cpu GETHSHARDING_PERFWATCH_LEDGER="$pw_led" \
    python -m gethsharding_tpu.perfwatch --run --check >/dev/null 2>&1
then
    if ! JAX_PLATFORMS=cpu GETHSHARDING_PERFWATCH_LEDGER="$pw_led" \
        python -m gethsharding_tpu.perfwatch --run --check >/dev/null 2>&1
    then
        echo "perfwatch smoke FAILED: clean rerun after the injected" \
             "record still trips the gate"
        fail=1
    fi
fi
rm -rf "$pw_tmp"
[ "$fail" = 0 ] && echo "perfwatch smoke OK: gate passes clean, trips on" \
    "the injected slowdown, heals on the clean rerun"

# -- devscope smoke: the device introspection plane end to end — an RPC
# server whose shard_profileStart/Stop toggles a sampling session (the
# collapsed-stack download must be non-empty), and a StatusServer node
# whose /profile control route, /profile/stacks download, /status
# devscope section and devscope/* Prometheus rows all answer
echo "== devscope smoke (profile toggle over RPC + devscope surfaces)"
ds_tmp=$(mktemp -d)
JAX_PLATFORMS=cpu GETHSHARDING_DEVSCOPE_PROFILE_DIR="$ds_tmp/profile" \
GETHSHARDING_PERFWATCH_DIR="$ds_tmp/blackbox" \
GETHSHARDING_PERFWATCH_LEDGER="$ds_tmp/ledger.jsonl" \
python - <<'PY' || fail=1
import json
import time
import urllib.request

# 1. the RPC face: toggle a sampler session on a chain-style RPCServer
from gethsharding_tpu.params import Config
from gethsharding_tpu.rpc.client import RPCClient
from gethsharding_tpu.rpc.server import RPCServer
from gethsharding_tpu.smc.chain import SimulatedMainchain

server = RPCServer(SimulatedMainchain(config=Config()))
server.start()
client = RPCClient(*server.address)
started = client.call("shard_profileStart", "sampler", 400)
assert started.get("started"), started
again = client.call("shard_profileStart", "sampler", 400)
assert again.get("already_running"), again
deadline = time.monotonic() + 5.0
while time.monotonic() < deadline:  # sample the RPC threads themselves
    client.call("shard_blockNumber")
    if client.call("shard_profileStacks"):
        break
stopped = client.call("shard_profileStop")
assert stopped.get("stopped"), stopped
stacks = client.call("shard_profileStacks")
assert stacks and "gethsharding" in stacks, (
    f"collapsed-stack download empty or foreign: {stacks[:120]!r}")
status = client.call("shard_devscopeStatus")
assert status["profiler"]["sessions"] >= 1, status
client.close()
server.stop()
print("devscope RPC toggle OK:", len(stacks.splitlines()), "stack lines")

# 2. the node face: /profile control + stacks download + prom rows
from gethsharding_tpu.node.backend import ShardNode
from gethsharding_tpu.node.http_status import StatusServer
from gethsharding_tpu import devscope

devscope.boot()
node = ShardNode(actor="observer", txpool_interval=None, http_port=0)
node.start()
try:
    port = node.service(StatusServer).port

    def get(path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as resp:
            return resp.read().decode()

    out = json.loads(get("/profile?action=start&mode=sampler&hz=400"))
    assert out.get("started"), out
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        get("/status")  # keep threads busy so the sampler sees stacks
        if get("/profile/stacks"):
            break
    out = json.loads(get("/profile?action=stop"))
    assert out.get("stopped"), out
    stacks = get("/profile/stacks")
    assert stacks, "/profile/stacks empty after a sampled session"
    status = json.loads(get("/status"))
    assert "devscope" in status, sorted(status)
    assert status["devscope"]["memory"]["running"], status["devscope"]
    prom = get("/metrics?format=prom")
    for row in ("devscope_mem_polls", "devscope_profiler_sessions",
                "devscope_compile_count"):
        assert row in prom, f"{row} missing from the prom exposition"
finally:
    node.stop()
    devscope.shutdown()
print("devscope smoke OK: RPC + /profile toggles, stacks served,"
      " prom rows present")
PY
rm -rf "$ds_tmp"

# -- elastic fleet smoke: the runtime-membership control plane against
# real processes — 2 chain_server replicas behind 2 peered frontends,
# one frontend killed -9 under FrontendPool traffic (actors must fail
# over), a third replica added LIVE via shard_addReplica on the
# survivor's peer and gossiped across before the kill; asserts the
# survivor converged (epoch bumped, added endpoint healthy) with zero
# wrong answers throughout
echo "== elastic fleet smoke (2 frontends + 2 replicas, kill one + live add)"
JAX_PLATFORMS=cpu python - <<'PYEOF' || fail=1
import os, sys, threading, time
sys.path.insert(0, "scripts")
from serving_stress import _spawn, _free_port, build_cases

env = {**os.environ, "JAX_PLATFORMS": "cpu"}
procs = []
try:
    eps = []
    for _ in range(3):  # 2 registered at boot + 1 added live
        p, a = _spawn([sys.executable,
                       "-m", "gethsharding_tpu.rpc.chain_server",
                       "--sigbackend", "python", "--verbosity", "error"],
                      env=env)
        procs.append(p)
        eps.append("%s:%d" % (a["host"], a["port"]))
    pa, pb = _free_port(), _free_port()

    def fe(port, peer):
        return _spawn([sys.executable, "-m",
                       "gethsharding_tpu.fleet.frontend",
                       "--verbosity", "error", "--port", str(port),
                       "--health-interval", "0.1",
                       "--gossip-interval", "0.25",
                       "--peer", "127.0.0.1:%d" % peer,
                       "--replica", eps[0], "--replica", eps[1]],
                      env=env)

    fa_p, fa = fe(pa, pb)
    procs.append(fa_p)
    fb_p, fb = fe(pb, pa)
    procs.append(fb_p)

    from gethsharding_tpu.rpc.client import FrontendPool, RPCClient
    # primary on B so the kill is felt by the pool, not just a spare
    pool = FrontendPool(["%s:%d" % (fb["host"], fb["port"]),
                         "%s:%d" % (fa["host"], fa["port"])], timeout=10.0)
    cases = build_cases(32)
    stop = threading.Event()
    wrong, done = [], [0]

    def traffic():
        i = 0
        while not stop.is_set():
            d, s, w = cases[i % len(cases)]
            i += 1
            try:
                got = pool.ecrecover_addresses([d], [s])
            except Exception:
                continue  # typed refusal/failover window
            if got != [w]:
                wrong.append(got)
                return
            done[0] += 1
            time.sleep(0.005)

    t = threading.Thread(target=traffic, daemon=True)
    t.start()
    time.sleep(1.0)

    # live add through frontend B (the pool's primary), then assert the
    # epoch GOSSIPS to frontend A
    res = pool.call("shard_addReplica", eps[2])
    assert res["name"] == eps[2] and res["epoch"] >= 1, res
    ra = RPCClient(fa["host"], fa["port"])
    snap = {}
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        snap = ra.call("shard_membership")
        if eps[2] in snap.get("endpoints", []) and snap.get("epoch", 0) >= 1:
            break
        time.sleep(0.2)
    assert eps[2] in snap.get("endpoints", []), snap

    # kill frontend B -9 mid-traffic: actors must fail over to A
    before = done[0]
    fb_p.kill()
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline and not (
            pool.failovers >= 1 and done[0] > before):
        time.sleep(0.2)
    assert pool.failovers >= 1, "pool never failed over"
    assert done[0] > before, "no verified traffic after the kill"

    # convergence on the survivor: the live-added replica reaches
    # HEALTHY in A's sweep and answers are still correct
    state = {}
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        status = ra.call("shard_fleetStatus")
        state = {n: s["state"] for n, s in status["replicas"].items()}
        if state.get(eps[2]) == "healthy" and len(state) == 3:
            break
        time.sleep(0.2)
    assert state.get(eps[2]) == "healthy", state
    stop.set()
    t.join(timeout=10)
    assert not wrong, wrong
    ra.close()
    pool.close()
    print("elastic smoke OK: add gossiped, kill -9 failed over,"
          " survivor converged (%d verified)" % done[0])
finally:
    for p in procs:
        p.terminate()
PYEOF

# -- shardlint: the repo-wide static analysis gate (jit-purity,
# host-sync, lock-order, race-guard, layering, backend-contract,
# thread-lifecycle, flag-doc, export-completeness) — fails on any
# finding outside the committed baseline
# (gethsharding_tpu/analysis/baseline.json)
echo "== shardlint (static analysis gate)"
JAX_PLATFORMS=cpu python -m gethsharding_tpu.analysis || fail=1

# -- lockcheck + racecheck smoke: the concurrency-heavy suites run
# ONCE with BOTH runtime recorders patched in (one run on purpose:
# GETHSHARDING_RACECHECK requires the lock recorder anyway, so both
# session gates fire — re-running the suites under LOCKCHECK alone
# would duplicate ~26 s for no extra coverage). The lockcheck gate
# fails the run on any observed AB/BA inversion or an order that
# contradicts the static lock graph; the racecheck gate fails it on
# any runtime write lockset that CONTRADICTS the static race-guard
# model (a "guarded" attr written shared with no lock, an "init-only"
# attr written from two threads) and prints the honest coverage gaps —
# statically-flagged attrs this run never drove shared.
echo "== lockcheck+racecheck smoke (fleet/serving/concurrency under both recorders)"
GETHSHARDING_LOCKCHECK=1 GETHSHARDING_RACECHECK=1 JAX_PLATFORMS=cpu \
    python -m pytest \
    tests/test_concurrency.py tests/test_serving.py tests/test_fleet.py \
    tests/test_fleet_frontend.py tests/test_fleet_elastic.py \
    -q --no-header -m 'not slow' || fail=1

for f in tests/test_*.py; do
    echo "== $f"
    python -m pytest "$f" -q --no-header || fail=1
done
exit $fail
