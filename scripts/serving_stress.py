#!/usr/bin/env python
"""Soak driver for the serving tier — single-backend or fleet.

Default mode (unchanged since PR 1): M client threads hammer ONE
serving backend with small ecrecover requests for a fixed duration,
verifying EVERY result against the known signer (zero-divergence soak,
not just throughput), while a reporter prints one JSON stats line per
interval:

    python scripts/serving_stress.py --clients 32 --duration 30 \
        --policy shed --queue-cap 256 --flush-us 500

Fleet traffic-model mode (`--replicas N`): an in-process fleet of N
breaker-guarded serving replicas behind the shard-aware router
(gethsharding_tpu/fleet/), driven by a production-shaped load model:

- **admission-class mix** (`--classes interactive=8,bulk_audit=3,...`):
  each client thread carries a class; bulk/catchup issue multi-row
  requests, interactive issues 1-row requests and must never be shed;
- **diurnal curve** (`--diurnal-s`): the active-client fraction swings
  sinusoidally between 30% and 100% over one period — load is a wave,
  not a constant;
- **hot-shard skew** (`--hot-shard`): that fraction of catchup/bulk
  requests carries ONE affinity key, overloading a single replica the
  way a popular shard does;
- **thundering herd** (`--herd-at`): at that second every client
  pauses, then re-bursts simultaneously — the reconnect stampede;
- optional seeded chaos (`--chaos-trip`) trips replica r0's breaker
  mid-soak so the drain→probe→re-enter cycle runs under load.

Per-class p99 latencies are reported and (when `--slo-interactive-ms`
etc. are nonzero) GATED. Exit code 1 on any divergence, hung client, interactive shed, or
SLO breach.

Light-client traffic model (`--light-clients N`): N threads drive
1-row `das_verify_multiproofs` requests (polynomial-multiproof DAS,
das/pcs.py) through the fleet router as interactive-class traffic
under their own `light` tenant quota bucket. Every row has a KNOWN
verdict (honest openings and tampered evals interleaved), so the soak
gates on correctness — one wrong verdict fails the run — as well as
the das_light p99 when `--slo-interactive-ms` is set.

Frontend process mode (`--frontend`, with `--replicas N`): the REAL
topology — N `chain_server` replica processes, one standalone
`fleet.frontend` process balancing them (hedging armed via
`--hedge-ms`), M client threads dialing the FRONTEND over JSON-RPC.
Every answer is verified against the known signer; the summary reports
the frontend's hedge win/waste rates from `shard_fleetStatus`. Exit 1
on any divergence or hung client.

Elastic closed-loop mode (`--elastic`): 2 chain_server replicas
behind TWO peered frontend processes (frontend A runs the SLO-driven
autoscaler), clients on `rpc.client.FrontendPool` driving a 10x
diurnal swing; frontend B is killed -9 mid-swing. Gates: zero
incorrect verdicts, pool failover observed, the autoscaler scales OUT
at the peak AND back IN during the trough (countered via
`shard_fleetStatus`), interactive p99 under `--slo-interactive-ms`.
Emits a `fleet_elastic` workload record through
`perfwatch.record_bench`.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gethsharding_tpu import metrics
from gethsharding_tpu.crypto import secp256k1 as ecdsa
from gethsharding_tpu.crypto.keccak import keccak256
from gethsharding_tpu.serving import (ServingConfig, ServingOverloadError,
                                      ServingSigBackend)
from gethsharding_tpu.sigbackend import get_backend

CLASS_MIX_DEFAULT = "interactive=8,bulk_audit=3,catchup_replay=1"
CLASS_ROWS = {"interactive": 1, "bulk_audit": 4, "catchup_replay": 8}


def build_cases(n: int):
    """n distinct (digest, sig65, expected address) rows."""
    cases = []
    for i in range(n):
        priv = int.from_bytes(keccak256(b"soak-%d" % i), "big") % ecdsa.N
        digest = keccak256(b"soak-msg-%d" % i)
        cases.append((digest, ecdsa.sign(digest, priv).to_bytes65(),
                      ecdsa.priv_to_address(priv)))
    return cases


def parse_class_mix(spec: str):
    mix = []
    for part in filter(None, (p.strip() for p in spec.split(","))):
        name, _, weight = part.partition("=")
        mix.extend([name] * int(weight or 1))
    return mix


def percentile(samples, q: float) -> float:
    if not samples:
        return 0.0
    ordered = sorted(samples)
    return ordered[min(int(q * len(ordered)), len(ordered) - 1)]


def run_single(args) -> int:
    """The original single-backend soak (PR 1 behavior, unchanged)."""
    cases = build_cases(args.cases)
    serving = ServingSigBackend(
        get_backend(args.backend),
        ServingConfig(max_batch=args.max_batch, flush_us=args.flush_us,
                      queue_cap=args.queue_cap, policy=args.policy))

    done = [0] * args.clients
    shed = [0] * args.clients
    divergences: list = []
    deadline = time.monotonic() + args.duration
    stop = threading.Event()

    def client(c: int) -> None:
        i = c  # stagger the case cycle per client
        while time.monotonic() < deadline and not stop.is_set():
            digest, sig, want = cases[i % len(cases)]
            i += args.clients
            try:
                got = serving.ecrecover_addresses([digest], [sig])
            except ServingOverloadError:
                shed[c] += 1
                continue
            if got != [want]:
                divergences.append((c, i))
                stop.set()
                return
            done[c] += 1

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(args.clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()

    wait_timer = metrics.DEFAULT_REGISTRY.timer("serving/ecrecover/wait_time")
    last_done = 0
    while time.monotonic() < deadline and not stop.is_set():
        time.sleep(min(args.report_interval, deadline - time.monotonic())
                   if deadline > time.monotonic() else 0)
        total = sum(done)
        print(json.dumps({
            "t_s": round(time.monotonic() - t0, 1),
            "done": total,
            "rate": round((total - last_done) / args.report_interval, 1),
            "shed": sum(shed),
            "dispatches": serving.dispatch_count,
            "coalesce_ratio": round(total / max(1, serving.dispatch_count),
                                    1),
            "queue_depth": serving.batcher.queue_depth_rows(
                "ecrecover_addresses"),
            "wait_p50_ms": round(wait_timer.percentile(0.5) * 1e3, 2),
        }), flush=True)
        last_done = total

    for t in threads:
        t.join(timeout=30)
    hung = [t for t in threads if t.is_alive()]
    wall = time.monotonic() - t0
    serving.close()

    total = sum(done)
    print(json.dumps({
        "summary": True,
        "clients": args.clients,
        "policy": args.policy,
        "wall_s": round(wall, 2),
        "done": total,
        "rate": round(total / wall, 1) if wall else 0.0,
        "shed": sum(shed),
        "dispatches": serving.dispatch_count,
        "coalesce_ratio": round(total / max(1, serving.dispatch_count), 1),
        "divergences": len(divergences),
        "hung_clients": len(hung),
    }), flush=True)
    return 1 if divergences or hung else 0


def build_fleet(args):
    """N breaker-guarded serving replicas behind the shard router; r0
    optionally carries a seeded chaos schedule that trips its breaker
    mid-soak."""
    from gethsharding_tpu.fleet import FleetRouter, Replica, RouterSigBackend
    from gethsharding_tpu.resilience.breaker import (CircuitBreaker,
                                                     FailoverSigBackend)
    from gethsharding_tpu.resilience.chaos import (ChaosSchedule,
                                                   ChaosSigBackend)

    servings, replicas, schedule = [], [], None
    for i in range(args.replicas):
        inner = get_backend(args.backend)
        if i == 0 and args.chaos_trip > 0:
            start = args.chaos_trip
            schedule = ChaosSchedule(
                seed=args.chaos_seed,
                rules={"backend.ecrecover_addresses":
                       lambda idx, start=start: start <= idx < start + 8})
            inner = ChaosSigBackend(inner, schedule)
        serving = ServingSigBackend(
            inner,
            ServingConfig(max_batch=args.max_batch, flush_us=args.flush_us,
                          queue_cap=args.queue_cap, policy=args.policy))
        servings.append(serving)
        replicas.append(Replica(
            f"r{i}",
            FailoverSigBackend(
                serving, get_backend("python"),
                breaker=CircuitBreaker(name=f"soak-r{i}",
                                       fault_threshold=3,
                                       reset_s=args.breaker_reset_s))))
    router = FleetRouter(replicas, health_interval_s=0.05)
    return router, RouterSigBackend(router), servings, replicas, schedule


def run_fleet(args) -> int:
    from gethsharding_tpu.fleet import AllReplicasDraining
    from gethsharding_tpu.serving.classes import CLASS_INTERACTIVE

    router, back, servings, replicas, schedule = build_fleet(args)
    cases = build_cases(args.cases)
    mix = parse_class_mix(args.classes)
    lat = {name: [] for name in CLASS_ROWS}
    done = {name: 0 for name in CLASS_ROWS}
    shed = {name: 0 for name in CLASS_ROWS}
    divergences: list = []
    stop = threading.Event()
    t0 = time.monotonic()
    deadline = t0 + args.duration
    herd_gate = threading.Event()
    herd_gate.set()

    def active_fraction(now: float) -> float:
        if args.diurnal_s <= 0:
            return 1.0
        phase = 2 * math.pi * ((now - t0) % args.diurnal_s) / args.diurnal_s
        return 0.65 + 0.35 * math.sin(phase)  # 30%..100%

    def client(c: int) -> None:
        klass = mix[c % len(mix)]
        rows = CLASS_ROWS[klass]
        rng_i = c
        while time.monotonic() < deadline and not stop.is_set():
            herd_gate.wait()
            # diurnal gating: clients beyond the active fraction sleep
            if (c / max(1, args.clients)) > active_fraction(
                    time.monotonic()):
                time.sleep(0.01)
                continue
            batch = [cases[(rng_i + j) % len(cases)] for j in range(rows)]
            rng_i += rows * args.clients
            # hot-shard skew applies to the bulk planes
            affinity = None
            if klass != CLASS_INTERACTIVE \
                    and (rng_i % 100) < args.hot_shard * 100:
                affinity = "hot-shard"
            t_req = time.monotonic()
            try:
                got = router.call("ecrecover_addresses",
                                  [b[0] for b in batch],
                                  [b[1] for b in batch],
                                  affinity=affinity, klass=klass)
            except (ServingOverloadError, AllReplicasDraining):
                shed[klass] += 1
                continue
            lat[klass].append(time.monotonic() - t_req)
            if got != [b[2] for b in batch]:
                divergences.append((c, rng_i))
                stop.set()
                return
            done[klass] += 1
            if klass == CLASS_INTERACTIVE:
                time.sleep(0.001)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(args.clients)]
    for t in threads:
        t.start()

    herd_done = args.herd_at <= 0
    last_report = t0
    while time.monotonic() < deadline and not stop.is_set():
        time.sleep(0.1)
        now = time.monotonic()
        if not herd_done and now - t0 >= args.herd_at:
            # thundering herd: everyone disconnects, then re-bursts at
            # the same instant
            herd_gate.clear()
            time.sleep(args.herd_pause_s)
            herd_gate.set()
            herd_done = True
            print(json.dumps({"herd": True, "t_s": round(now - t0, 1)}),
                  flush=True)
        if now - last_report >= args.report_interval:
            last_report = now
            print(json.dumps({
                "t_s": round(now - t0, 1),
                "active_fraction": round(active_fraction(now), 2),
                "done": dict(done),
                "shed": dict(shed),
                "states": {name: state["state"]
                           for name, state in router.states().items()},
            }), flush=True)

    for t in threads:
        t.join(timeout=60)
    hung = [t for t in threads if t.is_alive()]
    stop.set()

    # let a tripped replica finish its probe-driven re-entry
    reentered = True
    if schedule is not None:
        reentry_deadline = time.monotonic() + 10
        while replicas[0].state != "healthy" \
                and time.monotonic() < reentry_deadline:
            router.refresh(force=True)
            time.sleep(0.05)
        reentered = replicas[0].state == "healthy"

    shed_by_class = {name: 0 for name in CLASS_ROWS}
    for serving in servings:
        for klass, count in serving.batcher.shed_by_class().items():
            shed_by_class[klass] += count
    p99_ms = {name: round(percentile(samples, 0.99) * 1e3, 2)
              for name, samples in lat.items()}
    slo = {"interactive": args.slo_interactive_ms,
           "bulk_audit": args.slo_bulk_ms,
           "catchup_replay": args.slo_catchup_ms}
    slo_breaches = [name for name, limit in slo.items()
                    if limit > 0 and p99_ms[name] > limit]

    summary = {
        "summary": True,
        "fleet": True,
        "replicas": args.replicas,
        "clients": args.clients,
        "wall_s": round(time.monotonic() - t0, 2),
        "done": dict(done),
        "caller_shed": dict(shed),
        "replica_shed_by_class": shed_by_class,
        "p99_ms": p99_ms,
        "slo_ms": slo,
        "slo_breaches": slo_breaches,
        "divergences": len(divergences),
        "hung_clients": len(hung),
        "interactive_shed": shed["interactive"]
        + shed_by_class["interactive"],
        "drain_events": replicas[0].drain_events,
        "reentries": replicas[0].reentries,
        "chaos_injected": (0 if schedule is None else
                           schedule.injected.get(
                               "backend.ecrecover_addresses", 0)),
        "reentered": reentered,
        "states": {name: state["state"]
                   for name, state in router.states().items()},
    }
    print(json.dumps(summary), flush=True)
    for serving in servings:
        serving.close()

    failed = bool(divergences or hung or slo_breaches
                  or summary["interactive_shed"]
                  or (schedule is not None
                      and (summary["drain_events"] < 1 or not reentered)))
    return 1 if failed else 0


def build_poly_cases(n_cases: int, k: int):
    """Known-verdict multiproof rows: honest openings (expected True)
    interleaved with tampered evals (expected False) — a light-client
    check whose CORRECTNESS the soak verifies on every response, not
    just its latency."""
    import random as _random

    from gethsharding_tpu.das import pcs

    rng = _random.Random(7)
    cases = []
    for i in range(n_cases):
        n = 12
        values = [rng.randrange(pcs.N) for _ in range(n)]
        indices = sorted(rng.sample(range(n), min(k, n)))
        proof, evals = pcs.open_multi(values, indices)
        commitment = pcs.g1_to_bytes(pcs.commit(values))
        proof_bytes = pcs.g1_to_bytes(proof)
        cases.append((commitment, indices, evals, proof_bytes, n, True))
        if i % 2:
            bad = list(evals)
            bad[0] = (bad[0] + 1) % pcs.N
            cases.append((commitment, indices, bad, proof_bytes, n,
                          False))
    return cases


def run_light_clients(args) -> int:
    """The light-client sampling tier under load: M client threads
    drive 1-row `das_verify_multiproofs` requests through the fleet
    router as INTERACTIVE traffic under their own tenant quota bucket
    (`tenant="light"`), every verdict checked against the known truth.
    Gates: zero incorrect verdicts, zero hung clients, and (when
    `--slo-interactive-ms` is nonzero) the das_light p99. Latencies
    also feed the process `das_light` SLO objective (slo/tracker.py),
    so /status on a long-lived node shows the same series."""
    from gethsharding_tpu import slo
    from gethsharding_tpu.fleet import AllReplicasDraining

    router, _back, servings, _replicas, _schedule = build_fleet(args)
    cases = build_poly_cases(args.cases if args.cases <= 16 else 8,
                             args.light_k)
    lat: list = []
    done = [0]
    incorrect: list = []
    shed = [0]
    stop = threading.Event()
    t0 = time.monotonic()
    deadline = t0 + args.duration

    def client(c: int) -> None:
        i = c
        while time.monotonic() < deadline and not stop.is_set():
            commitment, indices, evals, proof, n, want = \
                cases[i % len(cases)]
            i += args.light_clients
            t_req = time.monotonic()
            try:
                got = router.call("das_verify_multiproofs",
                                  [commitment], [indices], [evals],
                                  [proof], [n],
                                  affinity=commitment.hex(),
                                  klass="interactive", tenant="light")
            except (ServingOverloadError, AllReplicasDraining):
                shed[0] += 1
                slo.record("das_light", ok=False)
                continue
            elapsed = time.monotonic() - t_req
            lat.append(elapsed)
            slo.record("das_light", ok=got == [want],
                       latency_s=elapsed)
            if got != [want]:
                incorrect.append((c, i, got, want))
                stop.set()
                return
            done[0] += 1

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(args.light_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=args.duration + 60)
    hung = [t for t in threads if t.is_alive()]
    stop.set()
    wall = time.monotonic() - t0

    quota_rejections = sum(s.batcher.quota_rejections()
                           for s in servings)
    p99_ms = round(percentile(lat, 0.99) * 1e3, 2)
    slo_breach = bool(args.slo_interactive_ms > 0
                      and p99_ms > args.slo_interactive_ms)
    summary = {
        "summary": True,
        "light_clients": args.light_clients,
        "replicas": args.replicas,
        "wall_s": round(wall, 2),
        "done": done[0],
        "rate": round(done[0] / wall, 2) if wall else 0.0,
        "shed": shed[0],
        "quota_rejections": quota_rejections,
        "p99_ms": p99_ms,
        "slo_ms": args.slo_interactive_ms,
        "slo_breach": slo_breach,
        "incorrect_verdicts": len(incorrect),
        "hung_clients": len(hung),
    }
    print(json.dumps(summary), flush=True)
    for serving in servings:
        serving.close()
    return 1 if incorrect or hung or slo_breach else 0


def _spawn(cmd, env=None):
    import subprocess

    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True,
                            env=env or os.environ.copy())
    line = proc.stdout.readline().strip()
    if not line:
        proc.terminate()
        raise RuntimeError(f"{cmd[:4]}... printed no address line")
    addr = json.loads(line)
    return proc, addr


def run_frontend(args) -> int:
    """The cross-process topology soak: N chain_server replicas + ONE
    standalone frontend process, clients dialing the frontend."""
    from gethsharding_tpu.rpc import codec
    from gethsharding_tpu.rpc.client import RPCClient, RPCError

    n = max(2, args.replicas)
    env = {**os.environ}
    env.setdefault("JAX_PLATFORMS", "cpu")
    replicas, endpoints = [], []
    frontend = None
    try:
        for _ in range(n):
            proc, addr = _spawn(
                [sys.executable, "-m", "gethsharding_tpu.rpc.chain_server",
                 "--sigbackend", "python", "--verbosity", "error"],
                env=env)
            replicas.append(proc)
            endpoints.append("%s:%d" % (addr["host"], addr["port"]))
        fe_cmd = [sys.executable, "-m", "gethsharding_tpu.fleet.frontend",
                  "--verbosity", "error",
                  "--health-interval", "0.1",
                  "--fleet-hedge-ms", str(args.hedge_ms)]
        for endpoint in endpoints:
            fe_cmd += ["--replica", endpoint]
        frontend, fe_addr = _spawn(fe_cmd, env=env)

        cases = build_cases(args.cases)
        done = [0] * args.clients
        divergences: list = []
        typed_errors = [0]
        stop = threading.Event()
        deadline = time.monotonic() + args.duration

        def client(c: int) -> None:
            rpc = RPCClient(fe_addr["host"], fe_addr["port"])
            i = c
            try:
                while time.monotonic() < deadline and not stop.is_set():
                    digest, sig, want = cases[i % len(cases)]
                    i += args.clients
                    try:
                        got = rpc.call("shard_ecrecover",
                                       [codec.enc_bytes(digest)],
                                       [codec.enc_bytes(sig)])
                    except RPCError:
                        typed_errors[0] += 1
                        continue
                    if got != [codec.enc_bytes(want)]:
                        divergences.append((c, i))
                        stop.set()
                        return
                    done[c] += 1
            finally:
                rpc.close()

        threads = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(args.clients)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=args.duration + 60)
        hung = [t for t in threads if t.is_alive()]
        wall = time.monotonic() - t0

        status_rpc = RPCClient(fe_addr["host"], fe_addr["port"])
        status = status_rpc.call("shard_fleetStatus")
        status_rpc.close()
        hedge = status["hedge"]
        total = sum(done)
        dispatches = total + hedge["issued"]
        summary = {
            "summary": True,
            "frontend": True,
            "replicas": n,
            "clients": args.clients,
            "wall_s": round(wall, 2),
            "done": total,
            "rate": round(total / wall, 1) if wall else 0.0,
            "typed_errors": typed_errors[0],
            "divergences": len(divergences),
            "hung_clients": len(hung),
            "hedge": hedge,
            "hedge_win_rate": round(
                hedge["won"] / max(1, hedge["issued"]), 3),
            "hedge_waste_rate": round(
                hedge["wasted"] / max(1, dispatches), 3),
            "replica_states": {name: s["state"]
                               for name, s in status["replicas"].items()},
        }
        print(json.dumps(summary), flush=True)
        return 1 if divergences or hung else 0
    finally:
        if frontend is not None:
            frontend.terminate()
        for proc in replicas:
            proc.terminate()


def _free_port() -> int:
    """Pre-pick a listening port (bind/release) so two frontends can be
    started with --peer pointing at each other before either is up."""
    import socket

    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    return port


def run_elastic(args) -> int:
    """The elastic closed-loop soak (ISSUE 20 acceptance): 2
    chain_server replica processes behind TWO peered frontend
    processes — frontend A runs the SLO-driven autoscaler — while
    clients on `rpc.client.FrontendPool` drive a 10x diurnal swing
    (offered load decays 100% -> 10% over the run). Mid-swing frontend
    B is killed -9; its clients must fail over to A without one
    incorrect verdict. The autoscaler must be OBSERVED acting in both
    directions: scale-OUT during the peak (sustained queue depth
    federated from the replicas' serving gauges) and scale-IN during
    the trough, both read back COUNTERED from frontend A's
    `shard_fleetStatus`. Gates: zero incorrect verdicts, zero hung
    clients, failovers >= 1, out >= 1 AND in >= 1, and (when
    `--slo-interactive-ms` is set) the interactive p99. The result is
    emitted as a `fleet_elastic` workload record through
    `perfwatch.record_bench` into the perf ledger."""
    from gethsharding_tpu.rpc.client import FrontendPool, RPCClient, RPCError

    n = max(2, args.replicas or 2)
    env = {**os.environ}
    env.setdefault("JAX_PLATFORMS", "cpu")
    procs: list = []
    frontends: list = []
    try:
        endpoints = []
        for _ in range(n):
            proc, addr = _spawn(
                [sys.executable, "-m", "gethsharding_tpu.rpc.chain_server",
                 "--sigbackend", "python", "--verbosity", "error"],
                env=env)
            procs.append(proc)
            endpoints.append("%s:%d" % (addr["host"], addr["port"]))

        # peered frontends need each other's address BEFORE either is
        # up: pre-pick both ports
        ports = (_free_port(), _free_port())
        scaler_env = {
            **env,
            "GETHSHARDING_AUTOSCALE_MIN": str(n),
            "GETHSHARDING_AUTOSCALE_MAX": str(n + 1),
            "GETHSHARDING_AUTOSCALE_INTERVAL_S": "0.25",
            "GETHSHARDING_AUTOSCALE_OUT_DEPTH": str(args.elastic_out_depth),
            "GETHSHARDING_AUTOSCALE_IN_DEPTH": "2",
            "GETHSHARDING_AUTOSCALE_SUSTAIN_S": "0.75",
            "GETHSHARDING_AUTOSCALE_COOLDOWN_S": "2.0",
        }

        def fe_cmd(port: int, peer_port: int, autoscale: bool):
            cmd = [sys.executable, "-m", "gethsharding_tpu.fleet.frontend",
                   "--verbosity", "error", "--port", str(port),
                   "--health-interval", "0.1",
                   "--gossip-interval", "0.25",
                   "--peer", "127.0.0.1:%d" % peer_port]
            for endpoint in endpoints:
                cmd += ["--replica", endpoint]
            if autoscale:
                cmd += ["--autoscale", "--autoscale-backend", "python"]
            return cmd

        fe_a, addr_a = _spawn(fe_cmd(ports[0], ports[1], True),
                              env=scaler_env)
        frontends.append(fe_a)
        fe_b, addr_b = _spawn(fe_cmd(ports[1], ports[0], False), env=env)
        frontends.append(fe_b)
        ep_a = "%s:%d" % (addr_a["host"], addr_a["port"])
        ep_b = "%s:%d" % (addr_b["host"], addr_b["port"])

        cases = build_cases(args.cases)
        done = [0] * args.clients
        lat: list = []
        lat_lock = threading.Lock()
        divergences: list = []
        typed_errors = [0]
        stop = threading.Event()
        t0 = time.monotonic()
        deadline = t0 + args.duration
        # half the clients hold B as their sticky primary so the kill
        # actually exercises pool failover, not just a spare
        pools = (FrontendPool([ep_a, ep_b], timeout=15.0),
                 FrontendPool([ep_b, ep_a], timeout=15.0))

        def active_fraction(now: float) -> float:
            # one peak->trough half-cycle: 100% offered at t0 decaying
            # to 10% at the deadline — the 10x diurnal swing the
            # autoscaler must absorb (out near the peak, in during the
            # trough)
            phase = min(1.0, max(0.0, (now - t0) / args.duration))
            return 0.55 + 0.45 * math.cos(math.pi * phase)

        def client(c: int) -> None:
            pool = pools[c % 2]
            i = c
            while time.monotonic() < deadline and not stop.is_set():
                if (c / max(1, args.clients)) > active_fraction(
                        time.monotonic()):
                    time.sleep(0.02)
                    continue
                digest, sig, want = cases[i % len(cases)]
                i += args.clients
                t_req = time.monotonic()
                try:
                    got = pool.ecrecover_addresses([digest], [sig])
                except (ConnectionError, TimeoutError, RPCError, OSError):
                    typed_errors[0] += 1
                    continue
                with lat_lock:
                    lat.append(time.monotonic() - t_req)
                if got != [want]:
                    divergences.append((c, i))
                    stop.set()
                    return
                done[c] += 1

        threads = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(args.clients)]
        for t in threads:
            t.start()

        killed = False
        last_report = t0
        while time.monotonic() < deadline and not stop.is_set():
            time.sleep(0.1)
            now = time.monotonic()
            if not killed and now - t0 >= args.duration / 2:
                fe_b.kill()  # SIGKILL: no drain notice, no goodbyes
                killed = True
                print(json.dumps({"killed_frontend": ep_b,
                                  "t_s": round(now - t0, 1)}), flush=True)
            if now - last_report >= args.report_interval:
                last_report = now
                print(json.dumps({
                    "t_s": round(now - t0, 1),
                    "active_fraction": round(active_fraction(now), 2),
                    "done": sum(done),
                    "typed_errors": typed_errors[0],
                    "failovers": sum(p.failovers for p in pools),
                }), flush=True)

        for t in threads:
            t.join(timeout=args.duration + 60)
        hung = [t for t in threads if t.is_alive()]
        stop.set()
        wall = time.monotonic() - t0

        # give the controller a calm tail to finish the scale-in leg
        # (trough depth ~0 once the clients stop) and reap the drained
        # spawn, then read the countered evidence off frontend A
        status = None
        status_rpc = RPCClient(addr_a["host"], addr_a["port"])
        try:
            settle_deadline = time.monotonic() + 15.0
            while time.monotonic() < settle_deadline:
                status = status_rpc.call("shard_fleetStatus")
                scale = status.get("autoscale") or {}
                if scale.get("out", 0) >= 1 and scale.get("in", 0) >= 1 \
                        and not scale.get("retiring"):
                    break
                time.sleep(0.25)
        finally:
            status_rpc.close()
        scale = (status or {}).get("autoscale") or {}
        membership = (status or {}).get("membership") or {}

        total = sum(done)
        failovers = sum(p.failovers for p in pools)
        for pool in pools:
            pool.close()
        p99_ms = round(percentile(lat, 0.99) * 1e3, 2)
        slo_breach = bool(args.slo_interactive_ms > 0
                          and p99_ms > args.slo_interactive_ms)
        summary = {
            "summary": True,
            "elastic": True,
            "replicas": n,
            "clients": args.clients,
            "wall_s": round(wall, 2),
            "done": total,
            "rate": round(total / wall, 1) if wall else 0.0,
            "typed_errors": typed_errors[0],
            "divergences": len(divergences),
            "hung_clients": len(hung),
            "frontend_killed": killed,
            "failovers": failovers,
            "scale_out": scale.get("out", 0),
            "scale_in": scale.get("in", 0),
            "scale_held": scale.get("held", 0),
            "epoch": membership.get("epoch", 0),
            "endpoints": membership.get("endpoints", []),
            "p99_ms": p99_ms,
            "slo_ms": args.slo_interactive_ms,
            "slo_breach": slo_breach,
        }
        print(json.dumps(summary), flush=True)

        failed = bool(divergences or hung or slo_breach
                      or failovers < 1
                      or summary["scale_out"] < 1
                      or summary["scale_in"] < 1)
        try:  # the perfwatch gate's fleet_elastic workload record
            from gethsharding_tpu.perfwatch import record_bench

            record_bench(
                "fleet_elastic_interactive_p99_ms", p99_ms, unit="ms",
                vs_baseline=(round(p99_ms / args.slo_interactive_ms, 4)
                             if args.slo_interactive_ms > 0 else None),
                workload="fleet_elastic", valid=not failed,
                extra={k: v for k, v in summary.items()
                       if k not in ("summary", "p99_ms", "endpoints")})
        except Exception as exc:  # noqa: BLE001 - ledger is best-effort
            print(json.dumps({"ledger_error": repr(exc)}), flush=True)
        return 1 if failed else 0
    finally:
        for proc in frontends:
            proc.terminate()
        for proc in frontends:
            try:
                proc.wait(timeout=10)
            except Exception:  # noqa: BLE001
                proc.kill()
        for proc in procs:
            proc.terminate()


def main() -> int:
    parser = argparse.ArgumentParser(
        description="soak the serving tier (single backend or fleet)")
    parser.add_argument("--clients", type=int, default=16)
    parser.add_argument("--duration", type=float, default=10.0,
                        help="seconds of offered load")
    parser.add_argument("--backend", default="python",
                        choices=("python", "jax"),
                        help="wrapped backend (jax needs an accelerator)")
    parser.add_argument("--max-batch", type=int, default=128)
    parser.add_argument("--flush-us", type=float, default=500.0)
    parser.add_argument("--queue-cap", type=int, default=4096)
    parser.add_argument("--policy", default="block",
                        choices=("block", "shed"))
    parser.add_argument("--report-interval", type=float, default=2.0)
    parser.add_argument("--cases", type=int, default=256,
                        help="distinct signed rows cycled by the clients")
    # -- fleet traffic model ------------------------------------------------
    parser.add_argument("--replicas", type=int, default=0,
                        help="> 0: run the FLEET soak — this many "
                             "breaker-guarded serving replicas behind "
                             "the shard router (gethsharding_tpu/fleet/)")
    parser.add_argument("--classes", default=CLASS_MIX_DEFAULT,
                        help="admission-class client mix, e.g. "
                             "'interactive=8,bulk_audit=3,"
                             "catchup_replay=1'")
    parser.add_argument("--diurnal-s", type=float, default=0.0,
                        help="sinusoidal load period in seconds (0 = "
                             "flat load): active clients swing 30%%-100%%")
    parser.add_argument("--hot-shard", type=float, default=0.0,
                        help="fraction of bulk/catchup requests keyed to "
                             "ONE hot affinity (0..1)")
    parser.add_argument("--herd-at", type=float, default=0.0,
                        help="seconds into the soak to fire a thundering-"
                             "herd reconnect burst (0 = off)")
    parser.add_argument("--herd-pause-s", type=float, default=0.3,
                        help="how long the herd holds its breath")
    parser.add_argument("--chaos-trip", type=int, default=0,
                        help="> 0: seed a chaos run of 8 consecutive "
                             "device faults on replica r0 starting at "
                             "this dispatch index — trips its breaker "
                             "mid-soak")
    parser.add_argument("--frontend", action="store_true",
                        help="cross-process mode: spawn --replicas N "
                             "chain_server processes plus ONE standalone "
                             "fleet.frontend process and drive traffic "
                             "through the frontend over JSON-RPC, "
                             "reporting hedge win/waste rates")
    parser.add_argument("--hedge-ms", type=float, default=15.0,
                        help="frontend mode: the frontend's "
                             "--fleet-hedge-ms floor")
    parser.add_argument("--elastic", action="store_true",
                        help="elastic closed-loop soak: 2 chain_server "
                             "replicas behind TWO peered frontends "
                             "(frontend A autoscaling), FrontendPool "
                             "clients riding a 10x diurnal swing, one "
                             "frontend killed -9 mid-swing; gates on "
                             "zero incorrect verdicts, pool failover, "
                             "and the autoscaler scaling out AND in")
    parser.add_argument("--elastic-out-depth", type=float, default=3.0,
                        help="elastic mode: the autoscaler's scale-out "
                             "queue-depth threshold "
                             "(GETHSHARDING_AUTOSCALE_OUT_DEPTH for "
                             "the spawned frontend)")
    parser.add_argument("--light-clients", type=int, default=0,
                        help="> 0: run the LIGHT-CLIENT soak — this many "
                             "threads drive 1-row das_verify_multiproofs "
                             "requests (known verdicts, tenant 'light', "
                             "interactive class) through a --replicas "
                             "fleet; exit 1 on any incorrect verdict, "
                             "hung client, or p99 SLO breach")
    parser.add_argument("--light-k", type=int, default=2,
                        help="sampled indices per light-client "
                             "multiproof row")
    parser.add_argument("--chaos-seed", type=int, default=11)
    parser.add_argument("--breaker-reset-s", type=float, default=0.5)
    parser.add_argument("--slo-interactive-ms", type=float, default=0.0,
                        help="gate: interactive p99 must stay under this "
                             "(0 = report only)")
    parser.add_argument("--slo-bulk-ms", type=float, default=0.0)
    parser.add_argument("--slo-catchup-ms", type=float, default=0.0)
    args = parser.parse_args()

    if args.elastic:
        return run_elastic(args)
    if args.frontend:
        return run_frontend(args)
    if args.light_clients > 0:
        args.replicas = max(1, args.replicas)
        return run_light_clients(args)
    if args.replicas > 0:
        return run_fleet(args)
    return run_single(args)


if __name__ == "__main__":
    raise SystemExit(main())
