"""Standalone mainchain process: `python -m gethsharding_tpu.rpc.chain_server`.

The dev-mode equivalent of the geth process the reference's actors dial
(`sharding/mainchain/utils.go:17` — one mainchain node, N actor
processes). Hosts a SimulatedMainchain behind an RPCServer; block
production is either timed (--blocktime) or driven remotely via the
shard_commit / shard_fastForward dev methods.

Prints one JSON line {"host": ..., "port": ..., "sigbackend": ...,
"device": ...} on stdout once listening, so a parent process (test
harness, orchestrator) can dial it and read which device answers:
"device" is the jax backend's record (platform / device_kind / count),
null for the scalar backend.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time

from gethsharding_tpu.params import Config
from gethsharding_tpu.rpc.server import RPCServer
from gethsharding_tpu.smc.chain import SimulatedMainchain


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="chain-server")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--periodlength", type=int, default=5)
    parser.add_argument("--quorum", type=int, default=None,
                        help="override QUORUM_SIZE (dev/test chains)")
    parser.add_argument("--shardcount", type=int, default=None)
    parser.add_argument("--networkid", type=int, default=None)
    parser.add_argument("--blocktime", type=float, default=0.0,
                        help="auto block production interval (0 = manual "
                             "via shard_commit / shard_fastForward)")
    parser.add_argument("--runtime", type=float, default=0.0,
                        help="seconds before exit (0 = forever)")
    parser.add_argument("--follow", default=None, metavar="HOST:PORT",
                        help="run as a FOLLOWER replicating the leader "
                             "chain process at HOST:PORT (headers "
                             "engine-verified, state via checkpoint "
                             "pull — smc/sync.py)")
    parser.add_argument("--sigbackend", default="python",
                        choices=("python", "jax", "failover-python",
                                 "failover-jax"),
                        help="backend behind the shard_ecrecover / "
                             "shard_verifyAggregates serving tier: handler "
                             "threads coalesce concurrent requests into "
                             "shared dispatches (jax = batched TPU "
                             "kernels); failover-* composes the serving "
                             "tier behind a circuit breaker over the "
                             "scalar fallback, and exports the breaker "
                             "state on shard_health so a fleet router "
                             "(gethsharding_tpu/fleet/) drains a tripped "
                             "replica")
    parser.add_argument("--mesh-devices", type=int, default=None,
                        help="lay the jax sigbackend over an N-device "
                             "1-D shard mesh (sets GETHSHARDING_MESH_"
                             "DEVICES before the backend is built; "
                             "1 = single device, the default)")
    parser.add_argument("--serving-watchdog-s", type=float, default=0.0,
                        help="dispatch watchdog deadline for the serving "
                             "tier (0 = off): a wedged device call fails "
                             "its batch with DeadlineExceeded — under "
                             "failover-* that is a breaker fault, and a "
                             "router retries the caller on the next "
                             "replica")
    parser.add_argument("--serving-quota-rows", type=int, default=None,
                        help="per-tenant queued-row quota in the serving "
                             "admission queues (default: "
                             "GETHSHARDING_TENANT_QUOTA_ROWS, 0 = off)")
    parser.add_argument("--chaos", default="", metavar="SPEC",
                        help="seeded chaos schedule at the backend/"
                             "dispatch seams (resilience/chaos.py) — the "
                             "router smoke trips one replica's breaker "
                             "with this")
    parser.add_argument("--soundness-rate", type=float, default=None,
                        help="continuous soundness spot-check rate for "
                             "this replica's serving planes (resilience/"
                             "soundness.py; default GETHSHARDING_"
                             "SOUNDNESS_RATE, 0 = off) — pair with "
                             "--sigbackend failover-* so a detected "
                             "silent corruption trips the breaker and "
                             "a fleet frontend drains the replica")
    parser.add_argument("--trace", action="store_true",
                        help="collect RPC-handler + serving-tier spans "
                             "(per-request queue/assembly/dispatch "
                             "attribution) in the in-memory tracer")
    parser.add_argument("--trace-out", default="",
                        help="write collected spans as Chrome trace_event "
                             "JSON at exit (Perfetto); implies --trace")
    parser.add_argument("--trace-ring", type=int, default=4096,
                        help="finished-span ring capacity")
    parser.add_argument("--fleettrace-export", default=None,
                        metavar="HOST:PORT",
                        help="ship finished spans to the fleettrace "
                             "collector at HOST:PORT (a fleet frontend "
                             "run with --fleettrace) so this replica's "
                             "spans join the cross-process trace trees; "
                             "implies --trace (default: GETHSHARDING_"
                             "FLEETTRACE_EXPORT)")
    parser.add_argument("--verbosity", default="warning")
    args = parser.parse_args(argv)

    logging.basicConfig(
        level=getattr(logging, args.verbosity.upper()),
        format="%(asctime)s %(levelname)-7s %(name)s "
               "[%(trace_id)s]  %(message)s",
        datefmt="%H:%M:%S")
    # log <-> trace correlation (same stamp as the sharding CLI): a
    # replica's warnings join against its /trace + RPC-stitched spans
    from gethsharding_tpu import tracing

    tracing.install_log_correlation()
    fleettrace_export = args.fleettrace_export
    if fleettrace_export is None:
        fleettrace_export = os.environ.get(
            "GETHSHARDING_FLEETTRACE_EXPORT") or None
    if args.trace or args.trace_out or fleettrace_export:
        tracing.enable(ring_spans=args.trace_ring)
    overrides = {"period_length": args.periodlength}
    if args.quorum is not None:
        overrides["quorum_size"] = args.quorum
    if args.shardcount is not None:
        overrides["shard_count"] = args.shardcount
    if args.networkid is not None:
        overrides["network_id"] = args.networkid
    config = Config(**overrides)
    backend = SimulatedMainchain(config=config)
    # the serving seam: verification RPCs coalesce across handler
    # threads onto the chosen backend. A replica composes explicitly —
    # device → (chaos) → serving → (failover) — so shard_health exports
    # the breaker state and a fleet router can drain a tripped replica;
    # the plain names keep the old lazy-wrap behavior.
    from gethsharding_tpu.serving import ServingConfig, ServingSigBackend
    from gethsharding_tpu.sigbackend import get_backend

    if args.mesh_devices is not None:
        # the jax factory reads the env var at build time, so the flag
        # must land before the first get_backend("jax") in this process
        os.environ["GETHSHARDING_MESH_DEVICES"] = str(args.mesh_devices)
    failover = args.sigbackend.startswith("failover-")
    inner_name = (args.sigbackend[len("failover-"):] if failover
                  else args.sigbackend)
    sig_backend = get_backend(inner_name)
    if args.chaos:
        from gethsharding_tpu.resilience.chaos import (ChaosSigBackend,
                                                       parse_spec)

        sig_backend = ChaosSigBackend(sig_backend, parse_spec(args.chaos))
    sig_backend = ServingSigBackend(sig_backend, ServingConfig(
        watchdog_s=args.serving_watchdog_s,
        tenant_quota_rows=args.serving_quota_rows))
    composed = sig_backend
    # the node CLI's composition order (node/backend.py): device →
    # chaos → serving → soundness → failover, so a detected silent
    # corruption is a primary fault the breaker (and through
    # shard_health, a fleet frontend) acts on
    soundness_rate = args.soundness_rate
    if soundness_rate is None:
        soundness_rate = float(
            os.environ.get("GETHSHARDING_SOUNDNESS_RATE", "0") or 0)
    if soundness_rate > 0:
        from gethsharding_tpu.resilience.soundness import (
            SpotCheckSigBackend)

        if not failover:
            logging.getLogger("chain-server").warning(
                "--soundness-rate without --sigbackend failover-*: a "
                "detected corruption raises to the caller instead of "
                "tripping a breaker")
        sig_backend = SpotCheckSigBackend(sig_backend,
                                          rate=soundness_rate)
    if failover:
        from gethsharding_tpu.resilience.breaker import FailoverSigBackend

        sig_backend = FailoverSigBackend(sig_backend,
                                         get_backend("python"))
    # boot the SLO tracker so this replica's shard_metrics snapshot
    # carries the slo/<class>/... series from the first federation
    # scrape (env-derived objectives; serving records the events)
    from gethsharding_tpu import slo

    slo.tracker()
    # device introspection plane: HBM poller + the devscope/* rows this
    # replica's shard_metrics snapshot federates; shard_profileStart /
    # shard_profileStop toggle on-demand profiling over the RPC below
    from gethsharding_tpu import devscope

    devscope.boot()
    # the collector's clock: runtime/gc/* in the same snapshot. After
    # each first compile it settles the heap, so that a request's full
    # collections stop walking what tracing and lowering left behind
    tracing.GC_CLOCK.install(settle_after=devscope.COMPILES)
    # fleettrace export plane: a background exporter drains this
    # replica's finished spans to the fleet frontend's collector, which
    # rebases them onto the frontend clock (handshake-measured skew)
    # and assembles the cross-process trace trees
    if fleettrace_export:
        from gethsharding_tpu import fleettrace

        fleettrace.boot_exporter(fleettrace_export,
                                 label="chain-%d" % os.getpid())
    server = RPCServer(backend, host=args.host, port=args.port,
                       sig_backend=sig_backend)
    server.start()
    follower = None
    if args.follow:
        from gethsharding_tpu.smc.sync import ChainFollower

        leader_host, leader_port = args.follow.rsplit(":", 1)
        follower = ChainFollower(backend, leader_host, int(leader_port))
        follower.start()
    from gethsharding_tpu.sigbackend import device_record_of

    print(json.dumps({"host": server.address[0], "port": server.address[1],
                      "sigbackend": args.sigbackend,
                      "device": device_record_of(sig_backend)}),
          flush=True)

    deadline = time.monotonic() + args.runtime if args.runtime else None
    try:
        while deadline is None or time.monotonic() < deadline:
            if args.blocktime > 0 and follower is None:
                time.sleep(args.blocktime)
                backend.commit()
            else:
                time.sleep(0.2)
    except KeyboardInterrupt:
        pass
    finally:
        if follower is not None:
            follower.stop()
        server.stop()
        if fleettrace_export:
            from gethsharding_tpu import fleettrace

            fleettrace.shutdown()
        devscope.shutdown()
        # the server never owned the injected composition: drain-and-
        # fail its queued serving futures here so no caller is stranded
        composed.close()
        if args.trace_out:
            from gethsharding_tpu import tracing

            try:
                tracing.write_chrome_trace(args.trace_out)
            except OSError:
                logging.getLogger("chain-server").warning(
                    "trace export to %s failed", args.trace_out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
