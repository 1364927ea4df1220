"""`tpu-sharding sharding` — the CLI entry point.

Parity: `cmd/geth/shardingcmd.go` (+ flags `cmd/utils/flags.go:536-549`):
`sharding --actor {notary,proposer,observer,light} --shardid N --deposit
--datadir PATH`. Additional dev-mode flags run an in-process simulated
mainchain with automatic block production, so a single command demonstrates
the full period pipeline (the reference needs a separate geth process).
"""

from __future__ import annotations

import argparse
import logging
import os
import sys
import time
from typing import List, Optional

from gethsharding_tpu.node.backend import ShardNode
from gethsharding_tpu.params import Config, ETHER
from gethsharding_tpu.smc.chain import SimulatedMainchain


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="tpu-sharding",
        description="TPU-native sharding client",
    )
    sub = parser.add_subparsers(dest="command", required=True)

    sharding = sub.add_parser(
        "sharding", help="run a sharding actor node"
    )
    sharding.add_argument("--actor", default="observer",
                          choices=("notary", "proposer", "observer", "light"),
                          help="what role to run (flags.go:542 ActorFlag)")
    sharding.add_argument("--shardid", type=int, default=0,
                          help="shard to operate on (flags.go:546)")
    sharding.add_argument("--deposit", action="store_true",
                          help="deposit 1000 ETH to join the notary pool "
                               "(flags.go:537)")
    sharding.add_argument("--datadir", default="",
                          help="data directory (in-memory DB if empty)")
    sharding.add_argument("--password", default=None,
                          help="password file or literal for the encrypted "
                               "keystore under <datadir>/keystore "
                               "(flags.go PasswordFileFlag); with --datadir "
                               "the node address survives restarts")
    sharding.add_argument("--periodlength", type=int, default=5)
    sharding.add_argument("--windback", type=int, default=0,
                          help="enforced windback depth: periods of prior "
                               "collation bodies a notary must hold before "
                               "voting (sharding/README.md)")
    sharding.add_argument("--blocktime", type=float, default=1.0,
                          help="dev-mode block production interval seconds")
    sharding.add_argument("--runtime", type=float, default=0.0,
                          help="seconds to run before exiting (0 = forever)")
    sharding.add_argument("--txinterval", type=float, default=5.0,
                          help="simulated txpool emission interval")
    sharding.add_argument("--sigbackend", default="python",
                          choices=("python", "jax", "failover-python",
                                   "failover-jax"),
                          help="signature verification backend: scalar host "
                               "crypto or batched TPU kernels (the "
                               "reference's native-crypto build seam); "
                               "failover-* puts the chosen backend behind "
                               "a circuit breaker over the scalar fallback "
                               "(gethsharding_tpu/resilience)")
    sharding.add_argument("--mesh-devices", type=int, default=None,
                          help="lay the jax sigbackend over an N-device "
                               "1-D shard mesh: committee audits run as "
                               "one pjit'd step with the vote-total "
                               "allreduce as the only cross-device "
                               "traffic (sets GETHSHARDING_MESH_DEVICES; "
                               "1 = single device, the default)")
    sharding.add_argument("--serving", action="store_true",
                          help="run signature verification through the "
                               "micro-batching serving tier: concurrent "
                               "callers' requests coalesce into shared "
                               "device dispatches (gethsharding_tpu/"
                               "serving/)")
    sharding.add_argument("--serving-max-batch", type=int, default=128,
                          help="flush a coalesced batch at this many rows "
                               "(rounded to a sigbackend bucket shape)")
    sharding.add_argument("--serving-flush-us", type=float, default=500.0,
                          help="deadline flush: a queued request waits at "
                               "most this many microseconds for company")
    sharding.add_argument("--serving-queue-cap", type=int, default=4096,
                          help="admission cap in rows; beyond it the "
                               "backpressure policy applies")
    sharding.add_argument("--serving-policy", default="block",
                          choices=("block", "shed"),
                          help="backpressure at the queue cap: block the "
                               "caller or shed with a fast error")
    sharding.add_argument("--serving-quota-rows", type=int, default=None,
                          help="per-tenant queued-row quota in the "
                               "serving admission queues (fleet tenant "
                               "isolation; default "
                               "GETHSHARDING_TENANT_QUOTA_ROWS, 0 = off)")
    sharding.add_argument("--serving-watchdog-s", type=float, default=0.0,
                          help="dispatch watchdog deadline in seconds: a "
                               "device call wedging the serving dispatch "
                               "thread longer than this fails its batch "
                               "with DeadlineExceeded and the dispatcher "
                               "restarts (0 = off)")
    sharding.add_argument("--da-mode", default="full",
                          choices=("full", "sampled"),
                          help="data-availability mode: 'full' fetches "
                               "whole collation bodies before voting "
                               "(the reference behavior); 'sampled' "
                               "erasure-extends bodies (proposer) and "
                               "votes on k sampled chunk proofs "
                               "verified in one batched device "
                               "dispatch (notary) — zero body bytes "
                               "(gethsharding_tpu/das/)")
    sharding.add_argument("--da-proofs", default="merkle",
                          choices=("merkle", "poly"),
                          help="sampled DA proof scheme: 'merkle' "
                               "ships a sibling path per sampled chunk "
                               "(keccak verify); 'poly' ships ONE "
                               "constant-size polynomial multiproof "
                               "per sampled collation, verified on "
                               "the batched bn256 pairing path "
                               "(das/pcs.py; dev SRS pinned by "
                               "GETHSHARDING_DAS_SRS_SEED)")
    sharding.add_argument("--da-samples", type=int, default=16,
                          help="sampled DA: chunks sampled per "
                               "(shard, period) availability check "
                               "(the k of the soundness table in "
                               "README 'Data availability sampling')")
    sharding.add_argument("--da-parity", type=float, default=0.5,
                          help="sampled DA: parity chunks as a ratio "
                               "of data chunks in the Reed-Solomon "
                               "extension (0.5 = body recoverable "
                               "from any 2/3 of the extended chunks)")
    sharding.add_argument("--chaos", default="",
                          metavar="SPEC",
                          help="deterministic chaos schedule, e.g. "
                               "'seed=7,backend.bls_verify_committees=2,"
                               "mainchain.collation_record=0.2': seeded "
                               "failure injection at the sig-backend and "
                               "mainchain-call seams (resilience/chaos.py; "
                               "pair with --sigbackend failover-* to watch "
                               "the breaker ride through it); a "
                               "'backend.*:mode=corrupt' entry injects "
                               "SILENT corruption (wrong results, no "
                               "exception) — pair with --soundness-rate "
                               "to watch the spot-checker catch it")
    sharding.add_argument("--soundness-rate", type=float, default=None,
                          metavar="RATE",
                          help="continuous integrity audit: spot-check "
                               "this fraction of sig-backend dispatches "
                               "by re-verifying a seeded-random row "
                               "subset against the scalar reference "
                               "(resilience/soundness.py; default off, "
                               "or GETHSHARDING_SOUNDNESS_RATE; a "
                               "detected mismatch is a primary fault — "
                               "pair with --sigbackend failover-* so "
                               "silent corruption trips the breaker)")
    sharding.add_argument("--fleet-frontend", default="",
                          metavar="HOST:PORT[,HOST:PORT...]",
                          help="dial a standalone fleet frontend "
                               "(python -m gethsharding_tpu.fleet."
                               "frontend) for ALL signature/DAS "
                               "verification instead of composing a "
                               "local backend: the actor's committee "
                               "audits and sample verdicts go over the "
                               "wire to the routed, hedged replica "
                               "fleet (serving/failover/soundness "
                               "composition then lives in the frontend "
                               "and its replicas, not in this process); "
                               "a comma-separated list names replicated "
                               "frontends — the actor fails over "
                               "between them (rpc.client.FrontendPool) "
                               "on the typed draining/connection-lost "
                               "taxonomy")
    sharding.add_argument("--verbosity", default="info",
                          choices=("debug", "info", "warning", "error"))
    sharding.add_argument("--metrics", action="store_true",
                          help="report the metrics registry periodically "
                               "and dump it at exit (metrics.go:22 gate)")
    sharding.add_argument("--metrics-interval", type=float, default=10.0)
    sharding.add_argument("--metrics-influx", default=None,
                          help="push line-protocol metrics to HOST:PORT "
                               "(UDP) or a file path (metrics/influxdb "
                               "exporter analog)")
    sharding.add_argument("--endpoint", default="",
                          metavar="HOST:PORT",
                          help="dial a running chain process instead of "
                               "hosting an in-process dev chain (the "
                               "`geth sharding [endpoint]` topology: N "
                               "actor processes, one mainchain)")
    sharding.add_argument("--http", type=int, default=None, metavar="PORT",
                          help="serve /healthz /metrics /status on this "
                               "port (dashboard/ethstats analog)")
    sharding.add_argument("--supervise", action="store_true",
                          help="watch actor services and restart crashed "
                               "ones as fresh instances (bounded; "
                               "node/service.go:78-83 restart semantics)")
    sharding.add_argument("--profile", default="",
                          help="write a JAX profiler trace to this directory "
                               "while running (the --pprof/--trace analog, "
                               "internal/debug/flags.go:40-90)")
    sharding.add_argument("--trace", action="store_true",
                          help="collect pipeline spans (notary/proposer/"
                               "txpool phases, serving queue_wait/"
                               "batch_assembly/device_dispatch attribution) "
                               "in the in-memory tracer; served at /trace "
                               "on the --http status server")
    sharding.add_argument("--trace-out", default="",
                          help="write the collected spans as Chrome "
                               "trace_event JSON at exit (open in Perfetto "
                               "or chrome://tracing); implies --trace")
    sharding.add_argument("--trace-ring", type=int, default=4096,
                          help="finished-span ring capacity (bounded "
                               "memory: oldest spans fall off)")
    sharding.add_argument("--fleettrace", action="store_true",
                          help="boot an in-process fleettrace collector: "
                               "assembles this node's spans (and any "
                               "replica exporting to it over "
                               "shard_traceExport) into cross-process "
                               "trace trees with tail-sampled SLO "
                               "exemplars and critical-path attribution; "
                               "served on /status and /metrics; implies "
                               "--trace")
    sharding.add_argument("--fleettrace-export", default=None,
                          metavar="HOST:PORT",
                          help="ship finished spans to the fleettrace "
                               "collector at HOST:PORT (a fleet frontend "
                               "or node run with --fleettrace); implies "
                               "--trace (default: GETHSHARDING_"
                               "FLEETTRACE_EXPORT)")
    attach = sub.add_parser(
        "attach", help="interactive console on a running chain process "
                       "(the geth attach / console analog)")
    attach.add_argument("--host", default="127.0.0.1")
    attach.add_argument("--port", type=int, required=True,
                        help="chain process RPC port")
    attach.add_argument("--verbosity", default="warning",
                        choices=("debug", "info", "warning", "error"))

    key = sub.add_parser("key", help="keystore tool (the ethkey analog)")
    key.add_argument("action", choices=("new", "list", "inspect"))
    key.add_argument("--keystore", required=True,
                     help="keystore directory")
    key.add_argument("--address", default=None)
    key.add_argument("--password", default=None,
                     help="password or password file (prompts if absent)")
    key.add_argument("--show-private", action="store_true")
    key.add_argument("--verbosity", default="warning",
                     choices=("debug", "info", "warning", "error"))

    faucet = sub.add_parser(
        "faucet", help="drip dev-chain funds to an address "
                       "(the cmd/faucet analog)")
    faucet.add_argument("--host", default="127.0.0.1")
    faucet.add_argument("--port", type=int, required=True,
                        help="chain process RPC port")
    faucet.add_argument("--address", required=True)
    faucet.add_argument("--amount", type=float, default=1000.0,
                        help="ETH to drip (default 1000)")
    faucet.add_argument("--verbosity", default="warning",
                        choices=("debug", "info", "warning", "error"))

    rlp = sub.add_parser("rlpdump",
                         help="pretty-print an RLP blob (rlpdump analog)")
    rlp.add_argument("data", help="hex string, or - for stdin")
    rlp.add_argument("--file", action="store_true",
                     help="treat DATA as a file path of raw bytes")
    rlp.add_argument("--verbosity", default="warning",
                     choices=("debug", "info", "warning", "error"))

    evm = sub.add_parser(
        "evm", help="run a JSON op scenario through the standalone SMC "
                    "engine, or raw bytecode through the general EVM "
                    "interpreter (the cmd/evm analog)")
    evm.add_argument("scenario", help="scenario JSON (tests/testdata/"
                                      "smc.json format), or hex bytecode "
                                      "with --code")
    evm.add_argument("--code", action="store_true",
                     help="SCENARIO is hex EVM bytecode: execute it with "
                          "the byzantium interpreter (core/vm.py)")
    evm.add_argument("--input", default="",
                     help="--code: hex calldata")
    evm.add_argument("--gas", type=int, default=10_000_000,
                     help="--code: gas budget")
    evm.add_argument("--trace", action="store_true",
                     help="print each op's outcome as it executes")
    evm.add_argument("--verbosity", default="warning",
                     choices=("debug", "info", "warning", "error"))

    bindgen = sub.add_parser(
        "bindgen", help="generate typed Python bindings from the chain "
                        "RPC method table (the abigen analog)")
    bindgen.add_argument("-o", "--out", default=None,
                         help="output file (default: stdout)")
    bindgen.add_argument("--verbosity", default="warning",
                         choices=("debug", "info", "warning", "error"))

    signer = sub.add_parser(
        "signer", help="external key-custody process with rules + audit "
                       "(the clef analog)")
    signer.add_argument("--keystore", required=True)
    signer.add_argument("--password", default=None,
                        help="password or password-file for the keystore")
    signer.add_argument("--port", type=int, default=0)
    signer.add_argument("--allow", default="",
                        help="comma-separated address allowlist "
                             "(empty = all keystore accounts)")
    signer.add_argument("--new", action="store_true",
                        help="create one account if the keystore is empty")
    signer.add_argument("--verbosity", default="warning",
                        choices=("debug", "info", "warning", "error"))

    devnet = sub.add_parser(
        "devnet", help="spin up a whole network as OS processes: one "
                       "chain + N supervised actors (the puppeth / "
                       "ExecAdapter role)")
    devnet.add_argument("--notaries", type=int, default=1)
    devnet.add_argument("--proposers", type=int, default=1)
    devnet.add_argument("--observers", type=int, default=0)
    devnet.add_argument("--lights", type=int, default=0)
    devnet.add_argument("--datadir", default="",
                        help="base dir for per-actor datadirs + logs "
                             "(empty = auto temp dir, kept after exit "
                             "for post-mortems)")
    devnet.add_argument("--blocktime", type=float, default=0.5)
    devnet.add_argument("--quorum", type=int, default=None)
    devnet.add_argument("--shardcount", type=int, default=None)
    devnet.add_argument("--sigbackend", default="python",
                        choices=("python", "jax", "failover-python",
                                 "failover-jax"))
    devnet.add_argument("--http-base", type=int, default=0,
                        help="first actor status port (0 = no status "
                             "servers); successive actors count up")
    devnet.add_argument("--runtime", type=float, default=0.0,
                        help="seconds before automatic shutdown "
                             "(0 = until SIGINT)")
    devnet.add_argument("--interval", type=float, default=2.0,
                        help="supervision/status cadence")
    devnet.add_argument("--verbosity", default="warning",
                        choices=("debug", "info", "warning", "error"))

    swarm = sub.add_parser(
        "swarm", help="content-addressed storage: up/get/serve over the "
                      "chunk tree + shardp2p netstore (cmd/swarm role)")
    swarm.add_argument("action", choices=("up", "get", "serve"))
    swarm.add_argument("target", nargs="?", default="",
                       help="up: file path; get: hex root key")
    swarm.add_argument("--datadir", required=True,
                       help="chunk DB directory (swarmchunks sqlite)")
    swarm.add_argument("--endpoint", default="",
                       help="relay HOST:PORT — serve chunks to / fetch "
                            "missing chunks from peers over shardp2p")
    swarm.add_argument("-o", "--output", default="-",
                       help="get: output file (- = stdout)")
    swarm.add_argument("--timeout", type=float, default=5.0,
                       help="per-chunk network fetch timeout")
    swarm.add_argument("--runtime", type=float, default=0.0,
                       help="serve: seconds before exit (0 = forever)")
    swarm.add_argument("--verbosity", default="warning",
                       choices=("debug", "info", "warning", "error"))
    return parser


def run_cli(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    logging.basicConfig(
        level=getattr(logging, args.verbosity.upper()),
        format="%(asctime)s %(levelname)-7s %(name)s "
               "[%(trace_id)s]  %(message)s",
        datefmt="%H:%M:%S",
    )
    # log <-> trace correlation: every record carries the emitting
    # context's trace id ('-' when none), so a warning from
    # sharding.node joins against /trace output by id
    from gethsharding_tpu import tracing as _tracing

    _tracing.install_log_correlation()
    if args.command == "sharding":
        return run_sharding_node(args)
    if args.command == "attach":
        from gethsharding_tpu.console import run_attach

        return run_attach(args.host, args.port)
    if args.command == "key":
        from gethsharding_tpu.tools import run_key

        return run_key(args)
    if args.command == "rlpdump":
        from gethsharding_tpu.tools import run_rlpdump

        return run_rlpdump(args)
    if args.command == "faucet":
        from gethsharding_tpu.tools import run_faucet

        return run_faucet(args)
    if args.command == "evm":
        from gethsharding_tpu.tools import run_evm

        return run_evm(args)
    if args.command == "bindgen":
        from gethsharding_tpu.tools import run_bindgen

        return run_bindgen(args)
    if args.command == "devnet":
        from gethsharding_tpu.devnet import run_devnet

        return run_devnet(args)
    if args.command == "swarm":
        from gethsharding_tpu.tools import run_swarm

        return run_swarm(args)
    if args.command == "signer":
        from gethsharding_tpu.signer import run_signer

        return run_signer(args)
    return 2


def run_sharding_node(args) -> int:
    if args.mesh_devices is not None:
        # the backend registry reads the env var at build time, so the
        # flag must land before any get_backend("jax") in this process
        os.environ["GETHSHARDING_MESH_DEVICES"] = str(args.mesh_devices)
    config = Config(period_length=args.periodlength,
                    windback_depth=args.windback)
    hub = None
    if args.endpoint:
        from gethsharding_tpu.p2p.remote import RemoteHub
        from gethsharding_tpu.rpc.client import RemoteMainchain

        host, _, port = args.endpoint.rpartition(":")
        if not port.isdigit():
            print(f"--endpoint must be HOST:PORT, got {args.endpoint!r}",
                  file=sys.stderr)
            return 2
        backend = RemoteMainchain.dial(host or "127.0.0.1", int(port))
        # the chain process owns the protocol constants: adopt its config
        # so every attached actor agrees on periods/committees (a stated
        # mismatch would silently skew period math — the real cross-
        # process divergence risk, not the network id)
        config = backend.chain_config(
            windback_depth=args.windback)
        hub = RemoteHub.dial(host or "127.0.0.1", int(port),
                             network_id=config.network_id)
    else:
        backend = SimulatedMainchain(config=config)
    password = args.password
    if password is not None:
        try:  # geth convention: --password usually names a file
            with open(password) as fh:
                password = fh.read().strip()
        except OSError:
            pass  # treat as a literal password
    serving_config = None
    if args.serving_watchdog_s and not args.serving:
        logging.getLogger("sharding.node").warning(
            "--serving-watchdog-s has no effect without --serving (the "
            "watchdog monitors the serving tier's dispatch thread) — "
            "hung-dispatch protection is NOT armed")
    if args.serving:
        from gethsharding_tpu.serving import ServingConfig

        serving_config = ServingConfig(
            max_batch=args.serving_max_batch,
            flush_us=args.serving_flush_us,
            queue_cap=args.serving_queue_cap,
            policy=args.serving_policy,
            watchdog_s=args.serving_watchdog_s,
            tenant_quota_rows=args.serving_quota_rows,
        )
    soundness_rate = args.soundness_rate
    if soundness_rate is None:
        soundness_rate = float(
            os.environ.get("GETHSHARDING_SOUNDNESS_RATE", "0") or 0)
    if soundness_rate > 0 and not args.sigbackend.startswith("failover-"):
        logging.getLogger("sharding.node").warning(
            "--soundness-rate without --sigbackend failover-*: a "
            "spot-check violation will RAISE into the calling actor "
            "instead of tripping a breaker onto the scalar fallback — "
            "silent corruption becomes loud, but nothing fails over")
    chaos_schedule = None
    raw_backend = backend
    if args.chaos:
        from gethsharding_tpu.resilience import chaos as chaos_mod

        chaos_schedule = chaos_mod.parse_spec(args.chaos)
        if soundness_rate <= 0 and any(
                mode == "corrupt"
                for mode in chaos_schedule.modes.values()):
            # silent corruption with nothing watching: the injected
            # wrong verdicts flow straight into consensus undetected —
            # the experiment tests nothing the operator can observe
            logging.getLogger("sharding.node").warning(
                "--chaos has mode=corrupt rules but the soundness "
                "spot-checker is off (--soundness-rate 0) — injected "
                "silent corruption will NOT be detected; pair with "
                "--soundness-rate (and --sigbackend failover-*) to "
                "watch it tripped")
        # the das.* seams (sample fetch, commitment fetch, parity
        # publish) only exist on a node running the sampled DA plane
        wired = ("mainchain", "backend", "dispatch")
        if args.da_mode == "sampled":
            wired = wired + ("das",)
        for seam in chaos_mod.unwired_seams(chaos_schedule, wired):
            logging.getLogger("sharding.node").warning(
                "chaos rule %r targets a seam this node never wraps "
                "(wired: %s) — it will inject nothing", seam,
                ", ".join(f"{w}.*" for w in wired))
        if any(seam == "mainchain" or seam.startswith("mainchain.")
               for seam in chaos_schedule.rules):
            # mainchain-call seam: the fault proxy fronts the chain
            # backend UNDER the client's retry executor, so retries are
            # exercised for real. The dev-mode block-production loop
            # below keeps driving the RAW chain — chaos targets the
            # actor's view of the chain, not the chain itself.
            backend = chaos_mod.wrap(backend, chaos_schedule, "mainchain")
            if int(os.environ.get("GETHSHARDING_CLIENT_RETRIES",
                                  "0")) <= 0:
                logging.getLogger("sharding.node").warning(
                    "chaos mainchain.* rules are wired under the "
                    "client's retry executor, but "
                    "GETHSHARDING_CLIENT_RETRIES is unset/0 — injected "
                    "mainchain faults will surface to the actors "
                    "unretried")
    if args.fleet_frontend and (args.serving or args.chaos
                                or args.sigbackend != "python"
                                or soundness_rate > 0):
        logging.getLogger("sharding.node").warning(
            "--fleet-frontend replaces the local verification "
            "composition: --serving/--sigbackend/--chaos/"
            "--soundness-rate apply inside the frontend's replicas, "
            "not this actor — local settings ignored for the "
            "verification planes")
    node = ShardNode(
        actor=args.actor,
        shard_id=args.shardid,
        config=config,
        backend=backend,
        data_dir=args.datadir,
        in_memory_db=args.datadir == "",
        deposit=args.deposit,
        txpool_interval=args.txinterval,
        sig_backend=args.sigbackend,
        password=password,
        supervise=args.supervise,
        http_port=args.http,
        hub=hub,
        serving=args.serving,
        serving_config=serving_config,
        chaos=chaos_schedule,
        soundness_rate=soundness_rate,
        da_mode=args.da_mode,
        da_samples=args.da_samples,
        da_parity=args.da_parity,
        da_proofs=args.da_proofs,
        fleet_frontend=args.fleet_frontend or None,
    )
    if hub is not None:
        # the node's public identity in the relay's peer table
        hub.account = node.client.account().hex_str
    # dev mode: fund the node account so --deposit can stake
    raw_backend.fund(node.client.account(), 2000 * ETHER)

    log = logging.getLogger("sharding.node")
    log.info("Starting sharding node: actor=%s shard=%d account=%s",
             args.actor, args.shardid, node.client.account().hex_str)

    reporter = None
    if args.metrics:
        from gethsharding_tpu.metrics import DEFAULT_REGISTRY, PeriodicReporter

        reporter = PeriodicReporter(interval=args.metrics_interval)
        reporter.start()
    influx = None
    if args.metrics_influx:
        from gethsharding_tpu.metrics import InfluxLineExporter

        host, _, port = args.metrics_influx.rpartition(":")
        if host and port.isdigit():
            influx = InfluxLineExporter(interval=args.metrics_interval,
                                        udp=(host, int(port)))
        else:
            influx = InfluxLineExporter(interval=args.metrics_interval,
                                        path=args.metrics_influx)
        influx.start()
    profiling = False
    if args.profile:
        # asked for a profile: a profiler that cannot start is a failure,
        # not a run that quietly records nothing
        import jax

        jax.profiler.start_trace(args.profile)
        profiling = True
    fleettrace_export = args.fleettrace_export
    if fleettrace_export is None:
        fleettrace_export = os.environ.get(
            "GETHSHARDING_FLEETTRACE_EXPORT") or None
    tracing_on = (args.trace or args.trace_out or args.fleettrace
                  or bool(fleettrace_export))
    if tracing_on:
        from gethsharding_tpu import tracing

        tracing.enable(ring_spans=args.trace_ring)
        log.info("span tracing enabled (ring %d)", args.trace_ring)
    # build the SLO tracker at boot (env-derived objectives) so the
    # slo/<class>/... gauges exist on /metrics and the Prometheus
    # exposition from the first scrape, not only after the first
    # recorded event — scrapers treat an absent series as "no SLO
    # plane", which a freshly-booted idle node is not
    from gethsharding_tpu import slo

    slo.tracker()
    # boot the device introspection plane (gethsharding_tpu/devscope):
    # the HBM memory poller starts publishing devscope/mem/* gauges and
    # the near-OOM census trigger arms; the compile watch and the
    # /profile //shard_profileStart surfaces are passive until used.
    # GETHSHARDING_DEVSCOPE=0 turns the poller off.
    from gethsharding_tpu import devscope

    devscope.boot()
    # the collector's clock: runtime/gc/* beside them. After each first
    # compile (an in-process --sigbackend jax) it settles the heap, so
    # that full collections stop walking what the compile left behind
    from gethsharding_tpu.tracing import GC_CLOCK

    GC_CLOCK.install(settle_after=devscope.COMPILES)
    # fleettrace: the collector assembles cross-process trace trees
    # (tail-sampled exemplars, critical-path attribution) out of this
    # node's spans plus any replica exporting to it; the exporter ships
    # this node's spans to a remote collector instead
    fleettrace_on = args.fleettrace or bool(fleettrace_export)
    if fleettrace_on:
        from gethsharding_tpu import fleettrace

        if args.fleettrace:
            fleettrace.boot_collector()
        if fleettrace_export:
            fleettrace.boot_exporter(fleettrace_export,
                                     label="node-%d" % os.getpid())

    node.start()

    deadline = time.monotonic() + args.runtime if args.runtime else None
    try:
        while deadline is None or time.monotonic() < deadline:
            time.sleep(args.blocktime)
            if args.endpoint:
                continue  # the chain process owns block production
            block = raw_backend.commit()
            if block.number % config.period_length == 0:
                log.info("period %d sealed (block %d)",
                         raw_backend.current_period(), block.number)
    except KeyboardInterrupt:
        log.info("interrupt received, shutting down")
    finally:
        node.stop()
        if fleettrace_on:
            from gethsharding_tpu import fleettrace

            fleettrace.shutdown()  # exporter final flush + sweep drain
        devscope.shutdown()  # poller thread + any live profile session
        if profiling:
            import jax

            jax.profiler.stop_trace()
        if tracing_on and args.trace_out:
            from gethsharding_tpu import tracing

            try:
                events = tracing.write_chrome_trace(args.trace_out)
                log.info("wrote %d trace events to %s (open in Perfetto)",
                         events, args.trace_out)
            except OSError as exc:
                log.warning("trace export failed: %s", exc)
        if reporter is not None:
            reporter.stop()
        if influx is not None:
            influx.stop()
    if args.metrics:
        from gethsharding_tpu.metrics import DEFAULT_REGISTRY

        for name, snap in DEFAULT_REGISTRY.snapshot().items():
            log.info("metric %s %s", name, snap)
    for error in node.errors():
        log.warning("service error: %s", error)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(run_cli())
