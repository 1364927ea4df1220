"""Driver benchmark: the five BASELINE.md configs on real hardware.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", "extra"}.

Headline metric (BASELINE config 3): aggregate notary-signature
verifications/sec across one 100-shard period. The workload is produced
by the PROTOCOL, not synthesized: a chain with 135 notaries registered
through the real registration path (derived BLS keys + proofs of
possession), 100 collation records added per period, and every committee
slot's vote BLS-signed over the real vote digest with the voter's real
key. What is measured is the live notary's `audit_period` — the
production code path that aggregates the period's votes and verifies all
shards in ONE batched pairing dispatch. (The reference's sampling quirk
yields ~1 eligible voter per shard per period; the bench populates all
135 committee slots per the protocol's documented committee intent.)

Extras: config 1 (single PairingCheck micro), config 2 (one 135-vote
aggregate), config 4 (collation replay, 1 shard), config 5 (the fused
1024-shard stress step) — skipped automatically when the backend is too
slow to fit the budget (hermetic CPU runs).

The kernel has build-time knobs whose best setting depends on the
backend (GETHSHARDING_TPU_LIMB_FORM = wide|exact, GETHSHARDING_TPU_CARRY
= scan|assoc, GETHSHARDING_TPU_CONV = shift|slices|gather|onehot|mxu8,
GETHSHARDING_TPU_PAIRCONV = xla|pallas, GETHSHARDING_TPU_PALLAS,
all read at import): the bench AUTOTUNES by re-executing itself
per configuration in a subprocess and reports the fastest, caching the
winner per backend in .bench_autotune.json. Signing workloads are cached
in .bench_workload.npz (first build ~3 min of host-side scalar crypto).

`bench.py --serving` measures the verification SERVING tier instead: M
concurrent clients x single-item requests coalesced into shared
dispatches vs the same clients driving the backend directly
(scripts/serving_stress.py is the open-ended soak form).

`bench.py --trace [--trace-out PATH]` runs the serving benchmark with
the span tracer on and writes a Chrome trace-event JSON (Perfetto):
per-request queue_wait / batch_assembly / device_dispatch attribution.
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import time

import numpy as np

SHARDS, COMMITTEE = 100, 135
REPO = os.path.dirname(os.path.abspath(__file__))

# ordered by prior: exact/scan won the r2 TPU sweep (then measured with
# the one-hot conv; `shift` — the module default — replaced it after CPU
# profiling showed gather memory-bound and onehot doing redundant MACs,
# but shift/slices had not been measured on TPU then, so this sweep
# decides). The assoc carry and the
# Pallas fused-normalize lost on TPU in r2 but stay as probes — backends
# change. If the sweep budget runs out, the best config measured so far
# wins.
CONFIGS = [
    {"GETHSHARDING_TPU_LIMB_FORM": "exact", "GETHSHARDING_TPU_CARRY": "scan"},
    # r4: the final-exponentiation mega-kernel (ops/pallas_finalexp.py) —
    # the whole ~250-op final exp as ONE pallas_call; the lever sized to
    # the latency-bound gap. Probed right after the
    # champion, composed with the champion's ambient knobs and with
    # relaxed normalize for the Miller side.
    {"GETHSHARDING_TPU_LIMB_FORM": "exact", "GETHSHARDING_TPU_CARRY": "scan",
     "GETHSHARDING_TPU_FINALEXP": "mega"},
    # the two-launch pairing check: Miller AND final exp each one kernel
    {"GETHSHARDING_TPU_LIMB_FORM": "exact", "GETHSHARDING_TPU_CARRY": "scan",
     "GETHSHARDING_TPU_FINALEXP": "mega", "GETHSHARDING_TPU_MILLER": "mega"},
    # the four-launch audit dispatch: aggregation kernels too
    {"GETHSHARDING_TPU_LIMB_FORM": "exact", "GETHSHARDING_TPU_CARRY": "scan",
     "GETHSHARDING_TPU_FINALEXP": "mega", "GETHSHARDING_TPU_MILLER": "mega",
     "GETHSHARDING_TPU_AGG": "mega"},
    # mega kernels composed over the slices conv ambient (the r4 TPU
    # sweep's non-mega champion) — the non-pairing remainder of the
    # dispatch also runs its fastest measured form
    {"GETHSHARDING_TPU_LIMB_FORM": "exact", "GETHSHARDING_TPU_CARRY": "scan",
     "GETHSHARDING_TPU_CONV": "slices",
     "GETHSHARDING_TPU_FINALEXP": "mega", "GETHSHARDING_TPU_MILLER": "mega",
     "GETHSHARDING_TPU_AGG": "mega"},
    {"GETHSHARDING_TPU_LIMB_FORM": "exact", "GETHSHARDING_TPU_CARRY": "scan",
     "GETHSHARDING_TPU_CONV": "slices",
     "GETHSHARDING_TPU_FINALEXP": "mega", "GETHSHARDING_TPU_MILLER": "mega"},
    # the uint16 wire format: halves host->device transfer bytes (12-bit
    # limbs in int32 waste 20 bits); widened on device, value-identical
    {"GETHSHARDING_TPU_LIMB_FORM": "exact", "GETHSHARDING_TPU_CARRY": "scan",
     "GETHSHARDING_TPU_FINALEXP": "mega", "GETHSHARDING_TPU_MILLER": "mega",
     "GETHSHARDING_TPU_WIRE": "u16"},
    {"GETHSHARDING_TPU_LIMB_FORM": "wide", "GETHSHARDING_TPU_NORM": "relaxed",
     "GETHSHARDING_TPU_FINALEXP": "mega"},
    # r3 additions, probed right after the champion: the statically
    # unrolled carry (straight-line fused code instead of an XLA While
    # per normalize), the fused Pallas pair-conv (never materializes the
    # product tensor in HBM), alone, + fused-normalize, and the
    # int8-plane MXU column contraction
    {"GETHSHARDING_TPU_LIMB_FORM": "exact",
     "GETHSHARDING_TPU_CARRY": "unroll"},
    # relaxed normalize: no exact carry ripple anywhere in the field ops
    # (wide form only; quasi-canonical limbs, see ops/limb.py)
    {"GETHSHARDING_TPU_LIMB_FORM": "wide", "GETHSHARDING_TPU_NORM": "relaxed"},
    {"GETHSHARDING_TPU_LIMB_FORM": "wide", "GETHSHARDING_TPU_NORM": "relaxed",
     "GETHSHARDING_TPU_SCAN_UNROLL": "8"},
    {"GETHSHARDING_TPU_LIMB_FORM": "exact", "GETHSHARDING_TPU_CARRY": "unroll",
     "GETHSHARDING_TPU_SCAN_UNROLL": "8"},
    {"GETHSHARDING_TPU_LIMB_FORM": "exact", "GETHSHARDING_TPU_CARRY": "scan",
     "GETHSHARDING_TPU_PAIRCONV": "pallas"},
    {"GETHSHARDING_TPU_LIMB_FORM": "exact", "GETHSHARDING_TPU_CARRY": "scan",
     "GETHSHARDING_TPU_PAIRCONV": "pallas", "GETHSHARDING_TPU_PALLAS": "1"},
    {"GETHSHARDING_TPU_LIMB_FORM": "exact", "GETHSHARDING_TPU_CARRY": "scan",
     "GETHSHARDING_TPU_CONV": "mxu8"},
    {"GETHSHARDING_TPU_LIMB_FORM": "exact", "GETHSHARDING_TPU_CARRY": "scan",
     "GETHSHARDING_TPU_CONV": "slices"},
    {"GETHSHARDING_TPU_LIMB_FORM": "wide", "GETHSHARDING_TPU_CARRY": "scan",
     "GETHSHARDING_TPU_PAIRCONV": "pallas"},
    {"GETHSHARDING_TPU_LIMB_FORM": "wide", "GETHSHARDING_TPU_CARRY": "scan"},
    {"GETHSHARDING_TPU_LIMB_FORM": "exact", "GETHSHARDING_TPU_CARRY": "scan",
     "GETHSHARDING_TPU_CONV": "onehot"},
    {"GETHSHARDING_TPU_LIMB_FORM": "exact", "GETHSHARDING_TPU_CARRY": "assoc"},
    {"GETHSHARDING_TPU_LIMB_FORM": "exact", "GETHSHARDING_TPU_CARRY": "scan",
     "GETHSHARDING_TPU_PALLAS": "1"},
    # LAST on purpose: the fully inlined PAIR_UNROLL kernels compile for
    # >35 min on XLA:CPU and may not fit the per-config probe timeout on
    # any backend; in a sweep they only run if budget remains
    {"GETHSHARDING_TPU_LIMB_FORM": "wide", "GETHSHARDING_TPU_NORM": "relaxed",
     "GETHSHARDING_TPU_PAIR_UNROLL": "finalexp"},
    {"GETHSHARDING_TPU_LIMB_FORM": "exact", "GETHSHARDING_TPU_CARRY": "unroll",
     "GETHSHARDING_TPU_PAIR_UNROLL": "1"},
    {"GETHSHARDING_TPU_LIMB_FORM": "exact", "GETHSHARDING_TPU_CARRY": "scan",
     "GETHSHARDING_TPU_PAIR_UNROLL": "1"},
    {"GETHSHARDING_TPU_LIMB_FORM": "wide", "GETHSHARDING_TPU_NORM": "relaxed",
     "GETHSHARDING_TPU_PAIR_UNROLL": "1"},
]

SWEEP_BUDGET_S = float(os.environ.get("GETHSHARDING_BENCH_BUDGET_S", "1200"))


# == protocol-generated workload (host scalar crypto, disk-cached) =========


def _workload_path() -> str:
    return os.path.join(REPO, ".bench_workload.npz")


def _point_to_bytes(p) -> np.ndarray:
    return np.frombuffer(p[0].to_bytes(32, "big") + p[1].to_bytes(32, "big"),
                         np.uint8)


def _point_from_bytes(b) -> tuple:
    raw = bytes(b)
    return (int.from_bytes(raw[:32], "big"), int.from_bytes(raw[32:], "big"))


def _bench_root(s: int, p: int):
    """The deterministic per-(shard, period) collation root — ONE formula
    shared by the identity builder and the cache-readiness gate (period 1
    keeps the original single-period formula so old caches stay valid)."""
    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.utils.hexbytes import Hash32

    return Hash32(keccak256(b"bench-root-%d" % s if p == 1
                            else b"bench-root-%d-p%d" % (s, p)))


def _bench_identities(k_periods: int = 1):
    """The deterministic identities + per-shard vote digests shared by the
    cache builder and the chain builder (single source of truth: a drift
    would silently invalidate the signature cache). With k_periods > 1
    the workload spans periods 1..K (the `audit_periods` catch-up form:
    BASELINE's protocol-level batching lever); period 1 keeps its
    original root formula so existing signature caches stay valid."""
    from gethsharding_tpu.mainchain.accounts import AccountManager
    from gethsharding_tpu.smc.state_machine import vote_digest

    manager = AccountManager()
    accounts = [manager.new_account(seed=b"bench-notary-%d" % i)
                for i in range(COMMITTEE)]
    periods = list(range(1, k_periods + 1))
    roots, digests = {}, {}
    for p in periods:
        roots[p] = [_bench_root(s, p) for s in range(SHARDS)]
        digests[p] = [bytes(vote_digest(s, p, roots[p][s]))
                      for s in range(SHARDS)]
    return manager, accounts, roots, digests, periods


def _sig_cache_keys(p: int) -> tuple:
    """npz keys for period p's signature block (period 1 keeps the
    original single-period keys so pre-existing caches stay valid)."""
    return (("vote_sigs", "digest0") if p == 1
            else (f"vote_sigs_p{p}", f"digest0_p{p}"))


def _sig_cache_entry_ok(cache, p: int, digest0: bytes) -> bool:
    """ONE validity rule for a cached period (key presence + protocol
    shape + pinned digest), shared by the loader and the readiness gate —
    a drift between the two would either silently skip K-period coverage
    or start the ~20-min rebuild inside a timed run. `cache` is any
    mapping of npz keys to arrays (dict or an open NpzFile)."""
    skey, dkey = _sig_cache_keys(p)
    if skey not in cache or dkey not in cache:
        return False
    return (cache[skey].shape == (SHARDS, COMMITTEE, 64)
            and bytes(cache[dkey]) == digest0)


def _load_or_build_vote_sigs(accounts, manager, digests) -> dict:
    """{period: (SHARDS, COMMITTEE, 64) uint8} — every committee slot's
    signature per shard digest, signed with the notary's real derived
    vote key. Cached per period (period 1 under the original npz keys, so
    pre-existing single-period caches are reused verbatim; building K=8
    extends a K=4 cache instead of restarting it)."""
    path = _workload_path()
    data: dict = {}
    try:
        with np.load(path) as cached:
            data = {key: cached[key] for key in cached.files}
    except (OSError, ValueError):
        data = {}
    out, dirty = {}, False
    for p in sorted(digests):
        dg = digests[p]
        skey, dkey = _sig_cache_keys(p)
        if _sig_cache_entry_ok(data, p, dg[0]):
            out[p] = data[skey]
            continue
        print(f"# building vote-signature workload for period {p} "
              f"({SHARDS}x{COMMITTEE} BLS signs, ~3 min once)...",
              file=sys.stderr)
        sigs = np.zeros((SHARDS, COMMITTEE, 64), np.uint8)
        for s in range(SHARDS):
            for i, acct in enumerate(accounts):
                sig = manager.bls_sign(acct.address, dg[s])
                sigs[s, i] = _point_to_bytes(sig)
        data[skey] = sigs
        data[dkey] = np.frombuffer(dg[0], np.uint8)
        out[p] = sigs
        dirty = True
    if dirty:
        try:
            np.savez_compressed(path, **data)
        except OSError:
            pass
    return out


def build_audit_workload(k_periods: int = 1):
    """A real chain at the end of K full 100-shard periods: registry,
    records, and signed votes all built through protocol objects. Returns
    (notary, periods) ready for repeated audit_period(s) calls."""
    from gethsharding_tpu.actors.notary import Notary
    from gethsharding_tpu.core.shard import Shard
    from gethsharding_tpu.db.kv import MemoryKV
    from gethsharding_tpu.mainchain.client import SMCClient
    from gethsharding_tpu.params import Config, ETHER
    from gethsharding_tpu.sigbackend import get_backend
    from gethsharding_tpu.smc.chain import SimulatedMainchain
    from gethsharding_tpu.smc.state_machine import VoteSig

    config = Config()  # protocol-scale: 100 shards, committee 135
    chain = SimulatedMainchain(config=config)
    manager, accounts, roots, digests, periods = _bench_identities(k_periods)
    for acct in accounts:
        chain.fund(acct.address, 2000 * ETHER)
        chain.register_notary(
            acct.address, bls_pubkey=acct.bls_pubkey,
            bls_pop=manager.bls_proof_of_possession(acct.address))
    sig_bytes = _load_or_build_vote_sigs(accounts, manager, digests)
    proposer = manager.new_account(seed=b"bench-proposer")
    for period in periods:
        chain.fast_forward(1)
        assert chain.current_period() == period, "identity/digest drift"
        for s in range(SHARDS):
            chain.add_header(proposer.address, s, period, roots[period][s])
        for s in range(SHARDS):
            record = chain.smc.collation_records[(s, period)]
            for i, acct in enumerate(accounts):
                record.vote_sigs[i] = VoteSig(
                    sig=_point_from_bytes(sig_bytes[period][s, i]),
                    signer=acct.address)
            record.vote_count = COMMITTEE
            record.is_elected = True
            chain.smc.last_approved_collation[s] = period
    chain.fast_forward(1)  # close the last period

    client = SMCClient(backend=chain, accounts=manager, account=accounts[0],
                       config=config)
    notary = Notary(client=client, shard=Shard(shard_id=0, shard_db=MemoryKV()),
                    config=config, sig_backend=get_backend("jax"))
    return notary, periods


# == measurements ==========================================================


def measure_single() -> dict:
    """Measure under the CURRENT env config; prints one stats JSON line."""
    _setup_bench_env()

    import jax

    notary, periods = build_audit_workload()
    period = periods[-1]

    # warm-up (compiles the bucketed batch shape) + correctness gate
    assert notary.audit_period(period) is True, "audit must be consistent"
    iters = 3
    t0 = time.perf_counter()
    for _ in range(iters):
        assert notary.audit_period(period) is True
    wall = (time.perf_counter() - t0) / iters
    # the verification dispatch itself (the BASELINE metric) — the audit
    # timer records only the sig-backend call
    dispatch = notary.m_audit_latency.percentile(0.5)
    sig_rate = SHARDS * COMMITTEE / dispatch

    stats = {
        "platform": jax.devices()[0].platform,
        "sig_rate": round(sig_rate, 1),
        "dispatch_s": round(dispatch, 4),
        "audit_wall_s": round(wall, 4),
        # the per-dispatch wire ledger rides in EVERY config's extras so
        # probe-42 transfer attribution is comparable across rounds
        # instead of living only in one-off probe artifacts
        **_wire_stats(notary.sig_backend),
        "knobs": _knob_snapshot(),
    }
    if os.environ.get("GETHSHARDING_BENCH_EXTRAS") == "1":
        # configs 1/2/4/5 run only for the sweep winner (main() re-invokes
        # with this flag) — not in every autotune subprocess
        stats.update(_measure_extras(dispatch))
    return stats


def _wire_stats(backend) -> dict:
    """The last dispatch's wire ledger (always on, no device sync):
    bytes over the host->device link + pk device-cache hit ratio."""
    wire = getattr(backend, "last_wire", None)
    if not wire:
        return {}
    return {
        "wire_bytes_per_dispatch": wire["wire_bytes"],
        "g2_wire_bytes_per_dispatch": wire["g2_wire_bytes"],
        "pk_cache_hit_ratio": round(
            wire["pk_hit_rows"] / max(1, wire["pk_rows"]), 4),
        "pk_resident": wire["resident"],
    }


def _kperiod_cache_ready(max_k: int = 8) -> bool:
    """True only when every period's cached signature block EXISTS, has
    the current (SHARDS, COMMITTEE, 64) shape, and its pinned digest
    matches the current identity formula — a stale cache (drifted seed /
    digest scheme / protocol shape) must read as not-ready, or the extras
    pass would start the ~20-min rebuild inside a timed run (the same
    checks _load_or_build_vote_sigs uses to decide a rebuild)."""
    from gethsharding_tpu.smc.state_machine import vote_digest

    try:
        with np.load(_workload_path()) as cached:
            for p in range(1, max_k + 1):
                if not _sig_cache_entry_ok(
                        cached, p, bytes(vote_digest(0, p,
                                                     _bench_root(0, p)))):
                    return False
    except (OSError, ValueError):
        return False
    return True


def _setup_bench_env() -> None:
    """The shared measurement preamble (CPU forcing + compile cache) —
    one definition so --single and --kperiod captures stay comparable."""
    if os.environ.get("GETHSHARDING_BENCH_CPU") == "1":
        # hermetic/offline runs: force the CPU backend before any init
        from gethsharding_tpu.parallel.virtual import force_virtual_cpu_devices

        force_virtual_cpu_devices(1)
    # persistent compile cache: first run pays the compiles, repeats
    # don't. ONE definition for every process of the program
    # (ops/device.py): JAX_COMPILATION_CACHE_DIR if set, else
    # <checkout>/.jax_cache.
    from gethsharding_tpu.ops.device import configure_compile_cache

    configure_compile_cache()


def _knob_snapshot() -> dict:
    """The active kernel knobs, so every output is self-describing."""
    return {key: val for key, val in os.environ.items()
            if key.startswith("GETHSHARDING_TPU_")}


def measure_kperiod(ks=None) -> dict:
    """sigs/sec vs K for the `audit_periods` K-period catch-up batch —
    the protocol-level lever (PERF.md): K periods' rows share ONE
    signature dispatch, so on a latency-bound kernel K periods cost
    nearly one. Reports the honest aggregate rate AND the per-dispatch /
    per-period latency for every K so the batching's latency cost is
    never hidden behind the throughput number."""
    _setup_bench_env()

    import jax

    if ks is None:
        ks = [int(x) for x in os.environ.get(
            "GETHSHARDING_BENCH_KLIST", "1,4,8").split(",")]
    ks = sorted(set(ks))
    notary, periods = build_audit_workload(max(ks))
    timer = notary.m_audit_latency
    sweep = []
    for k in ks:
        ps = periods[:k]
        res = notary.audit_periods(ps)  # warm-up compile + correctness gate
        assert all(res[p] is True for p in ps), "audit must be consistent"
        # isolate THIS K's dispatch samples: the registry timer is shared
        # across the whole sweep (reservoir 1024 >> samples taken here,
        # so the ring never wraps and the slice below is exact)
        base = len(timer._samples)
        iters = 3
        t0 = time.perf_counter()
        for _ in range(iters):
            res = notary.audit_periods(ps)
            assert all(res[p] is True for p in ps)
        wall = (time.perf_counter() - t0) / iters
        new = sorted(timer._samples[base:])
        dispatch = new[len(new) // 2]
        sweep.append({
            "k": k,
            "dispatch_s": round(dispatch, 4),
            "per_period_s": round(dispatch / k, 4),
            "audit_wall_s": round(wall, 4),
            "sig_rate": round(k * SHARDS * COMMITTEE / dispatch, 1),
            **_wire_stats(notary.sig_backend),
        })
        print(f"# K={k}: {sweep[-1]['sig_rate']:.1f} sigs/sec aggregate, "
              f"dispatch {dispatch:.4f} s ({sweep[-1]['per_period_s']:.4f} "
              f"s/period)", file=sys.stderr)
    best = max(sweep, key=lambda r: r["sig_rate"])
    return {
        "platform": jax.devices()[0].platform,
        "sig_rate": best["sig_rate"],
        "dispatch_s": best["dispatch_s"],
        "audit_wall_s": best["audit_wall_s"],
        "k_periods": best["k"],
        "per_period_dispatch_s": best["per_period_s"],
        "kperiod_sweep": sweep,
        "knobs": _knob_snapshot(),
    }


def _measure_extras(dispatch_s: float) -> dict:
    """Configs 1, 2, 4 (+5 when the backend is fast enough)."""
    import jax
    import jax.numpy as jnp

    from gethsharding_tpu.crypto import bn256 as ref
    from gethsharding_tpu.ops import bn256_jax as k
    # checked_pull: the block-vs-pull self-checked device->host pull —
    # a block that did not wait lands on the timer_suspect counter and
    # flags this run's ledger record invalid
    from gethsharding_tpu.perfwatch import checked_pull

    out = {}

    # config 1: single PairingCheck (e(aP,Q)e(-P,aQ) == 1), batch 1
    a = 1234567
    p1, q1 = ref.g1_mul(a, ref.G1_GEN), ref.G2_GEN
    p2, q2 = ref.g1_neg(ref.G1_GEN), ref.g2_mul(a, ref.G2_GEN)
    px, py, _ = k.g1_to_limbs([[p1, p2][i] for i in range(2)])
    qx, qy, _ = k.g2_to_limbs([[q1, q2][i] for i in range(2)])
    fn = jax.jit(k.pairing_check)
    args = (jnp.asarray(px)[None], jnp.asarray(py)[None],
            jnp.asarray(qx)[None], jnp.asarray(qy)[None],
            jnp.ones((1, 2), bool))
    assert bool(np.asarray(fn(*args))[0])
    t0 = time.perf_counter()
    for _ in range(3):
        r = fn(*args)
    checked_pull(r, op="bench/config1")  # real pull, self-checked
    out["config1_pairing_check_s"] = round((time.perf_counter() - t0) / 3, 4)

    # config 2: ONE 135-vote aggregate (batch 1 of the BLS kernel)
    header = b"bench-config2"
    keys = [ref.bls_keygen(bytes([i])) for i in range(4)]
    agg_sig = ref.bls_aggregate_sigs([ref.bls_sign(header, sk)
                                      for sk, _ in keys])
    agg_pk = ref.bls_aggregate_pks([pk for _, pk in keys])
    hx, hy, _ = k.g1_to_limbs([ref.hash_to_g1(header)])
    sx, sy, _ = k.g1_to_limbs([agg_sig])
    pkx, pky, _ = k.g2_to_limbs([agg_pk])
    fn2 = jax.jit(k.bls_verify_aggregate_batch)
    args2 = tuple(jnp.asarray(x) for x in (hx, hy, sx, sy, pkx, pky)) + (
        jnp.ones(1, bool),)
    assert bool(np.asarray(fn2(*args2))[0])
    t0 = time.perf_counter()
    for _ in range(3):
        r = fn2(*args2)
    checked_pull(r, op="bench/config2")  # real pull, self-checked
    out["config2_aggregate_verify_s"] = round((time.perf_counter() - t0) / 3,
                                              4)

    # config 4: collation replay, 1 shard x 64 txs
    from gethsharding_tpu.core import state_processor as sp
    from gethsharding_tpu.core.types import Transaction
    from gethsharding_tpu.crypto import secp256k1
    from gethsharding_tpu.ops import replay_jax

    n_txs = 64
    priv = 0xB0B
    sender = secp256k1.priv_to_address(priv)
    to = secp256k1.priv_to_address(0xA11CE)
    txs = [sp.sign_transaction(
        Transaction(nonce=i, gas_price=1, gas_limit=30000, to=to, value=1,
                    payload=b"x"), priv) for i in range(n_txs)]
    inp = replay_jax.build_replay_inputs(
        [txs], [{sender: sp.AccountState(balance=10 ** 12)}], [to])
    out4 = replay_jax.replay_batch(inp)
    assert bool(np.asarray(out4.statuses).all())
    t0 = time.perf_counter()
    for _ in range(3):
        out4 = replay_jax.replay_batch(inp)
    # the tiny statuses plane first as the self-checked barrier, then
    # the full-output transfer the HISTORICAL records timed — the
    # extra bool-plane RTT is noise next to the balances plane, while
    # changing the transferred volume would make every new
    # config4_replay_txs_per_s incomparable to the imported baseline
    checked_pull(out4.statuses, op="bench/config4")
    jax.device_get(out4)
    dt = (time.perf_counter() - t0) / 3
    out["config4_replay_txs_per_s"] = round(n_txs / dt, 1)

    # config 5: the fused 1024-shard stress step (addHeader + votes + BLS
    # + replay + all-reduce) — only when the backend is fast enough for
    # the 10x batch within the budget
    if dispatch_s < 2.0:
        from gethsharding_tpu.parallel.stress import (
            StressPipeline, build_stress_inputs)
        from gethsharding_tpu.params import Config

        n_shards = 1024
        inputs, pool, bh, sample_size, _ = build_stress_inputs(
            n_shards, votes_per_shard=2, txs_per_shard=1,
            committee_size=COMMITTEE)
        pipe = StressPipeline(config=Config(), mesh=None)
        res = pipe.run(inputs, pool, bh, 1, sample_size)
        jax.device_get(res.roots)
        t0 = time.perf_counter()
        res = pipe.run(inputs, pool, bh, 1, sample_size)
        checked_pull(res.roots, op="bench/config5")  # self-checked pull
        dt = time.perf_counter() - t0
        out["config5_stress_shards_per_s"] = round(n_shards / dt, 1)

    # the protocol-level lever (audit_periods K-period catch-up batching):
    # measured only when the K-period signature workload is ALREADY on
    # disk — the build is ~20 min of host scalar crypto, too much to
    # spend inside an extras pass
    if dispatch_s < 2.0:
        if _kperiod_cache_ready(8):
            try:
                kstats = measure_kperiod(ks=[4, 8])
                out["kperiod_sweep"] = kstats["kperiod_sweep"]
                out["kperiod_best_sig_rate"] = kstats["sig_rate"]
            except Exception as exc:  # extras must never sink the winner
                print(f"# kperiod extra failed: {exc!r}", file=sys.stderr)
    return out


# == device residency + overlap (bench.py --resident / --overlap) =========


def measure_resident() -> dict:
    """Transfer attribution for the device-resident pk planes: the same
    audit dispatched cold (empty device cache) then warm. With
    GETHSHARDING_TPU_RESIDENT on (the default) the warm path must ship
    ZERO G2 pubkey bytes — the steady-state acceptance ledger; with it
    off the cold/warm bytes are equal, giving the A/B for how much of
    the dispatch the transfer share is. Hermetic on CPU (the ledger is
    platform-independent); the 05_resident probe runs it on TPU where
    the byte saving becomes transfer time."""
    _setup_bench_env()

    import jax

    notary, periods = build_audit_workload()
    period = periods[-1]
    backend = notary.sig_backend

    # first dispatch: compile + cold-cache transfer
    assert notary.audit_period(period) is True, "audit must be consistent"
    cold = dict(backend.last_wire or {})
    iters = 3
    t0 = time.perf_counter()
    for _ in range(iters):
        assert notary.audit_period(period) is True
    wall = (time.perf_counter() - t0) / iters
    warm = dict(backend.last_wire or {})
    dispatch = notary.m_audit_latency.percentile(0.5)
    resident = bool(warm.get("resident"))
    if resident:
        # the ISSUE-4 acceptance bar: a steady-state audit with a warm
        # device cache transfers zero G2 pubkey bytes
        assert warm.get("g2_wire_bytes") == 0, (
            f"warm device cache must ship zero G2 bytes: {warm}")
    return {
        "platform": jax.devices()[0].platform,
        "sig_rate": round(SHARDS * COMMITTEE / dispatch, 1),
        "dispatch_s": round(dispatch, 4),
        "audit_wall_s": round(wall, 4),
        "resident": resident,
        "wire_bytes_cold": cold.get("wire_bytes"),
        "wire_bytes_warm": warm.get("wire_bytes"),
        "g2_wire_bytes_cold": cold.get("g2_wire_bytes"),
        "g2_wire_bytes_warm": warm.get("g2_wire_bytes"),
        "pk_hit_bytes_warm": warm.get("pk_hit_bytes"),
        "pk_cache_hit_ratio_warm": round(
            warm.get("pk_hit_rows", 0) / max(1, warm.get("pk_rows", 0)), 4),
        "knobs": _knob_snapshot(),
    }


def measure_overlap() -> dict:
    """Sequential vs overlapped K-period audit pipeline. Sequential:
    marshal period N+1 only after N's verdict returned (one
    `audit_period` per period). Overlapped: `audit_periods(...,
    overlap=True)` — the async backend face launches N's dispatch and
    returns, so N+1 marshals/stages while N executes on device.
    overlap_ratio = seq_wall / overlap_wall; the acceptance bar on
    hermetic CPU is 'no slower' (>= ~1.0 — host/device concurrency is
    core-bound there); on TPU the ratio bounds how much host marshal
    the dispatch hides."""
    _setup_bench_env()

    import jax

    k = int(os.environ.get("GETHSHARDING_BENCH_OVERLAP_K", "4"))
    notary, periods = build_audit_workload(k)
    ps = periods[:k]

    # warm-up: compile the per-period shape + correctness gate both ways
    seq_res = {p: notary.audit_period(p) for p in ps}
    assert all(v is True for v in seq_res.values()), "audit inconsistent"
    ov_res = notary.audit_periods(ps, overlap=True)
    assert ov_res == seq_res, "overlapped verdicts must be identical"

    iters = 2
    t0 = time.perf_counter()
    for _ in range(iters):
        for p in ps:
            assert notary.audit_period(p) is True
    seq_wall = (time.perf_counter() - t0) / iters
    t0 = time.perf_counter()
    for _ in range(iters):
        res = notary.audit_periods(ps, overlap=True)
        assert all(res[p] is True for p in ps)
    ov_wall = (time.perf_counter() - t0) / iters
    return {
        "platform": jax.devices()[0].platform,
        "k_periods": k,
        "seq_wall_s": round(seq_wall, 4),
        "overlap_wall_s": round(ov_wall, 4),
        "overlap_ratio": round(seq_wall / ov_wall, 4),
        "sig_rate": round(k * SHARDS * COMMITTEE / ov_wall, 1),
        **_wire_stats(notary.sig_backend),
        "knobs": _knob_snapshot(),
    }


# == mesh-parallel committee audit (bench.py --mesh) =======================


def measure_mesh() -> dict:
    """The multi-chip audit closed loop: the SAME seeded committee
    workload through the scalar reference, the single-device jax
    backend, and the D-device mesh backend — verdicts must be
    bit-identical all three ways (sync AND async), the compiled mesh
    step must contain exactly ONE cross-device collective (the
    vote-total allreduce, counted from the AOT HLO), and the per-device
    cache shards must own DISJOINT buffer sets in the devscope census.
    ALWAYS runs on forced virtual CPU devices (bit-identity and the
    collective count are platform-independent): its output names the
    `cpu` platform, and its rate is a CPU wall, not a device metric.
    The four-chip form is `chip_smoke.py`'s mesh leg."""
    from gethsharding_tpu.parallel.virtual import force_virtual_cpu_devices

    n_devices = int(os.environ.get("GETHSHARDING_BENCH_MESH_DEVICES", "8"))
    force_virtual_cpu_devices(n_devices)

    import jax

    from gethsharding_tpu import devscope
    from gethsharding_tpu.crypto import bn256 as bls
    from gethsharding_tpu.sigbackend import PythonSigBackend
    from gethsharding_tpu.sigbackend.dispatch import JaxSigBackend

    # every device gets pointful rows (rows == bucket, divisible by D);
    # committees stay small so the scalar reference pairing loop is
    # tractable inside the bench budget
    rows = 3 * n_devices
    committee = 3
    msgs = [bytes([7, i % 251]) * 16 for i in range(rows)]
    kps = [[bls.bls_keygen(bytes([i, j, 13]) * 8) for j in range(committee)]
           for i in range(rows)]
    pk_rows = [[pk for _, pk in row] for row in kps]
    sig_rows = [[bls.bls_sign(m, sk) for sk, _ in row]
                for m, row in zip(msgs, kps)]
    # adversarial rows: one empty committee (must reject) and one forged
    # vote (must reject) — bit-identity must hold on rejections too
    pk_rows[1], sig_rows[1] = [], []
    sig_rows[rows - 2] = list(sig_rows[rows - 2])
    sig_rows[rows - 2][0] = bls.bls_sign(b"\xde\xad" * 16,
                                         kps[rows - 2][0][0])
    keys = [f"mesh-row-{i}" for i in range(rows)]

    ref = PythonSigBackend().bls_verify_committees(msgs, sig_rows, pk_rows)
    single = JaxSigBackend(mesh_devices=1)
    got_single = single.bls_verify_committees(msgs, sig_rows, pk_rows,
                                              pk_row_keys=keys)
    mesh = JaxSigBackend(mesh_devices=n_devices)
    got_mesh = mesh.bls_verify_committees(msgs, sig_rows, pk_rows,
                                          pk_row_keys=keys)
    got_async = mesh.bls_verify_committees_async(
        msgs, sig_rows, pk_rows, pk_row_keys=keys).result()
    assert ref == got_single == got_mesh == got_async, (
        "mesh audit verdicts must be bit-identical to the single-device "
        f"and scalar paths: ref={ref} single={got_single} "
        f"mesh={got_mesh} async={got_async}")
    info = dict(mesh.last_mesh or {})
    # the transfer-ledger acceptance bar: ONE collective (the vote-total
    # allreduce) per compiled step, verdict plane really sharded
    assert info.get("collectives") == 1, (
        f"mesh step must contain exactly one cross-device collective: "
        f"{info}")
    assert info.get("verdict_devices") == n_devices, (
        f"verdict plane must shard over all {n_devices} devices: {info}")
    assert info.get("vote_total") == sum(ref), (
        f"psum vote total must equal the verdict sum: {info} vs "
        f"{sum(ref)}")

    # per-device cache shards: every shard owns buffers, registered
    # under its own census owner, and ownership is DISJOINT
    owner_names = [f"pk_plane_lru_shard{i}" for i in range(n_devices)]
    registered = set(devscope.owners())
    assert all(name in registered for name in owner_names), (
        f"every mesh shard must register a census owner: {registered}")
    shard_buf_ids = [
        {id(buf) for buf in mesh._mesh_shard_buffers(i)}
        for i in range(n_devices)]
    assert all(shard_buf_ids), "every shard must hold resident buffers"
    for i in range(n_devices):
        for j in range(i + 1, n_devices):
            overlap = shard_buf_ids[i] & shard_buf_ids[j]
            assert not overlap, (
                f"cache shards {i} and {j} share {len(overlap)} "
                f"buffers — per-device ownership must be disjoint")
    census = devscope.poller().census()
    owners_census = {name: census["owners"].get(name, {})
                     for name in owner_names}

    # steady-state rate: the memoized mesh batch repeats every period
    iters = int(os.environ.get("GETHSHARDING_BENCH_MESH_ITERS", "5"))
    t0 = time.perf_counter()
    for _ in range(iters):
        res = mesh.bls_verify_committees(msgs, sig_rows, pk_rows,
                                         pk_row_keys=keys)
    wall = (time.perf_counter() - t0) / iters
    assert res == ref, "steady-state mesh verdicts drifted"
    warm_wire = dict(mesh.last_wire or {})
    return {
        "platform": jax.devices()[0].platform,
        "backend": f"jax-mesh{n_devices}",
        "n_devices": n_devices,
        "rows": rows,
        "committee_width": committee,
        "sig_rate": round(rows * committee / wall, 1),
        "audits_per_s": round(1.0 / wall, 2),
        "audit_wall_s": round(wall, 5),
        "collectives_per_step": info["collectives"],
        "verdict_devices": info["verdict_devices"],
        "vote_total": info["vote_total"],
        "bucket": info["bucket"],
        "g2_wire_bytes_warm": warm_wire.get("g2_wire_bytes"),
        "pk_hit_rows_warm": warm_wire.get("pk_hit_rows"),
        "shard_census": {
            name: {"claimed_bytes": entry.get("claimed_bytes"),
                   "buffers": entry.get("buffers"),
                   "drifted": entry.get("drifted")}
            for name, entry in owners_census.items()},
        "knobs": _knob_snapshot(),
    }


# == fixed-base precomputation closed loop (bench.py --precomp) ============


def measure_precomp() -> dict:
    """The fixed-base pairing-precomputation closed loop: the SAME
    seeded committee workload through the scalar reference, the jax
    backend with GETHSHARDING_PRECOMP=1 (line tables resident in the
    device LRU) and with =0 (today's recompute path) — verdicts
    bit-identical on every path, sync AND async, hostile rows included
    (an empty committee, a forged vote, and a pk aggregate cancelled to
    INFINITY); the warm precomp audit ships ZERO G2 bytes; and the
    compiled precomp executable's HLO op census carries far fewer
    `multiply` ops than the recompute twin — proof the fixed-argument
    Miller point arithmetic is really absent from the warm dispatch,
    not merely hidden. Hermetic on CPU (bit-identity and the census are
    platform-independent); the 05_precomp probe runs the same loop on
    TPU where the skipped work becomes sigs/sec."""
    _setup_bench_env()

    import jax
    import jax.numpy as jnp

    from gethsharding_tpu.crypto import bn256 as bls
    from gethsharding_tpu.ops import bn256_jax as k
    from gethsharding_tpu.sigbackend import PythonSigBackend
    from gethsharding_tpu.sigbackend.dispatch import JaxSigBackend
    from gethsharding_tpu.sigbackend.layout import count_ops

    rows, committee = 8, 3
    msgs = [bytes([19, i % 251]) * 16 for i in range(rows)]
    kps = [[bls.bls_keygen(bytes([i + 1, j + 1, 37]) * 8)
            for j in range(committee)] for i in range(rows)]
    pk_rows = [[pk for _, pk in row] for row in kps]
    sig_rows = [[bls.bls_sign(m, sk) for sk, _ in row]
                for m, row in zip(msgs, kps)]
    # hostile rows: an empty committee, a forged vote, and a pk
    # aggregate cancelled to INFINITY (pk + (-pk)) — every rejection
    # must be identical on every path (the line table of a cancelled
    # aggregate is the infinity-marked zero table, never a stale accept)
    pk_rows[1], sig_rows[1] = [], []
    sig_rows[3] = list(sig_rows[3])
    sig_rows[3][0] = bls.bls_sign(b"some other collation header!!!!!",
                                  kps[3][0][0])
    pk_rows[5] = [pk_rows[5][0], bls.g2_neg(pk_rows[5][0])]
    sig_rows[5] = sig_rows[5][:2]
    keys = [f"precomp-row-{i}" for i in range(rows)]

    want = PythonSigBackend().bls_verify_committees(msgs, sig_rows, pk_rows)
    assert want[1] is False and want[3] is False and want[5] is False, (
        f"hostile rows must reject on the scalar reference: {want}")

    on = JaxSigBackend()  # GETHSHARDING_PRECOMP defaults on
    assert on._precomp, "precomp must default ON for the jax backend"
    got_cold = on.bls_verify_committees(msgs, sig_rows, pk_rows,
                                        pk_row_keys=keys)
    cold = dict(on.last_wire or {})
    got_warm = on.bls_verify_committees(msgs, sig_rows, pk_rows,
                                        pk_row_keys=keys)
    warm = dict(on.last_wire or {})
    got_async = on.bls_verify_committees_async(
        msgs, sig_rows, pk_rows, pk_row_keys=keys).result()
    prev = os.environ.get("GETHSHARDING_PRECOMP")
    os.environ["GETHSHARDING_PRECOMP"] = "0"
    try:
        off = JaxSigBackend()
    finally:
        if prev is None:
            del os.environ["GETHSHARDING_PRECOMP"]
        else:
            os.environ["GETHSHARDING_PRECOMP"] = prev
    got_off = off.bls_verify_committees(msgs, sig_rows, pk_rows,
                                        pk_row_keys=keys)
    assert want == got_cold == got_warm == got_async == got_off, (
        f"precomp verdicts must be bit-identical to the scalar + "
        f"recompute paths: ref={want} cold={got_cold} warm={got_warm} "
        f"async={got_async} recompute={got_off}")
    assert cold.get("precomp") is True and warm.get("precomp") is True
    assert off.last_wire.get("precomp") is False
    assert cold.get("g2_wire_bytes", 0) > 0, f"cold must ship G2: {cold}"
    # THE acceptance bar: a warm precomp audit ships zero G2 bytes AND
    # skips the point-arithmetic half of the Miller loop (census below)
    assert warm.get("g2_wire_bytes") == 0, (
        f"warm line tables must ship zero G2 bytes: {warm}")
    assert warm.get("pk_hit_rows") == sum(1 for r in pk_rows if r), warm

    # the op census: AOT-compile the precomp kernel and its recompute
    # twin at one small shape and compare `multiply` counts — the
    # fixed-argument point arithmetic (dbl/madd per schedule step +
    # the on-device G2 aggregation) must be absent from the warm
    # executable (same contract as the mesh collective count: counted
    # from the optimized HLO text, no hand-claimed speedup)
    nl = k.NLIMBS
    steps = k.LINE_TABLE_SHAPE[0]
    b, w = 1, 2
    z32 = functools.partial(jnp.zeros, dtype=jnp.int32)
    pre_args = (z32((b, nl)), z32((b, nl)),
                z32((b, w, nl)), z32((b, w, nl)), jnp.zeros((b, w), bool),
                z32((b, steps, 3, 2, nl)),
                jnp.zeros((b,), bool), jnp.zeros((b,), bool))
    rec_args = (z32((b, nl)), z32((b, nl)),
                z32((b, w, nl)), z32((b, w, nl)), jnp.zeros((b, w), bool),
                z32((b, w, 2, nl)), z32((b, w, 2, nl)),
                jnp.zeros((b, w), bool), jnp.zeros((b,), bool))
    pre_mul = count_ops(jax.jit(k.bls_verify_committee_precomp_batch)
                        .lower(*pre_args).compile().as_text(), "multiply")
    rec_mul = count_ops(jax.jit(k.bls_aggregate_verify_committee_batch)
                        .lower(*rec_args).compile().as_text(), "multiply")
    assert 0 < pre_mul < 0.7 * rec_mul, (
        f"precomp executable must drop the fixed-argument point "
        f"arithmetic: {pre_mul} multiplies vs recompute {rec_mul}")

    # steady-state warm rate (each dispatch DeviceTimer-stamped inside
    # the backend; a lying pull lands on the suspect counter and
    # invalidates this run's ledger record via _emit)
    n_sigs = sum(len(r) for r in sig_rows)
    iters = int(os.environ.get("GETHSHARDING_BENCH_PRECOMP_ITERS", "5"))
    t0 = time.perf_counter()
    for _ in range(iters):
        res = on.bls_verify_committees(msgs, sig_rows, pk_rows,
                                       pk_row_keys=keys)
    wall = (time.perf_counter() - t0) / iters
    assert res == want, "steady-state precomp verdicts drifted"
    t0 = time.perf_counter()
    for _ in range(iters):
        res = off.bls_verify_committees(msgs, sig_rows, pk_rows,
                                        pk_row_keys=keys)
    recompute_wall = (time.perf_counter() - t0) / iters
    assert res == want, "steady-state recompute verdicts drifted"

    stats = {
        "platform": jax.devices()[0].platform,
        "backend": "jax-precomp",
        "rows": rows,
        "n_sigs": n_sigs,
        "sig_rate": round(n_sigs / wall, 1),
        "audit_wall_s": round(wall, 5),
        "recompute_wall_s": round(recompute_wall, 5),
        "precomp_speedup": round(recompute_wall / wall, 4),
        "blocks": warm.get("blocks"),
        "g2_wire_bytes_cold": cold.get("g2_wire_bytes"),
        "g2_wire_bytes_warm": warm.get("g2_wire_bytes"),
        "pk_hit_rows_warm": warm.get("pk_hit_rows"),
        "hlo_multiplies_precomp": pre_mul,
        "hlo_multiplies_recompute": rec_mul,
        "hlo_multiply_ratio": round(pre_mul / rec_mul, 4),
        "knobs": _knob_snapshot(),
    }
    stats.update(_measure_precomp_stress())
    return stats


def _measure_precomp_stress() -> dict:
    """The config-5-style stress rider of the precomp loop: one fused
    multi-shard stress step (addHeader + votes + BLS + replay +
    all-reduce) under the precomp-era tree, sized down on CPU so the
    hermetic probe finishes inside its budget (the TPU probe runs the
    full 1024-shard shape). Failures never sink the closed loop — the
    stress record is a rider, the bit-identity loop is the contract."""
    import jax

    from gethsharding_tpu.perfwatch import checked_pull

    if os.environ.get("GETHSHARDING_BENCH_PRECOMP_STRESS", "1") != "1":
        return {}
    try:
        from gethsharding_tpu.parallel.stress import (StressPipeline,
                                                      build_stress_inputs)
        from gethsharding_tpu.params import Config

        on_tpu = jax.devices()[0].platform == "tpu"
        n_shards = int(os.environ.get(
            "GETHSHARDING_BENCH_PRECOMP_SHARDS",
            "1024" if on_tpu else "32"))
        committee_size = COMMITTEE if on_tpu else 8
        inputs, pool, bh, sample_size, _ = build_stress_inputs(
            n_shards, votes_per_shard=2, txs_per_shard=1,
            committee_size=committee_size)
        cfg = Config() if committee_size == Config().committee_size \
            else Config(committee_size=committee_size,
                        quorum_size=max(1, (2 * committee_size) // 3))
        pipe = StressPipeline(config=cfg, mesh=None)
        res = pipe.run(inputs, pool, bh, 1, sample_size)
        jax.device_get(res.roots)  # compile + warm-up
        t0 = time.perf_counter()
        res = pipe.run(inputs, pool, bh, 1, sample_size)
        checked_pull(res.roots, op="bench/precomp_config5")
        dt = time.perf_counter() - t0
        return {"config5_shards": n_shards,
                "config5_committee": committee_size,
                "config5_stress_shards_per_s": round(n_shards / dt, 1)}
    except Exception as exc:  # noqa: BLE001 - rider, not the contract
        print(f"# precomp config5 stress rider failed: {exc!r}",
              file=sys.stderr)
        return {}


def measure_composed() -> dict:
    """Resident + overlap (+ precomp) COMPOSED: the K-period overlapped
    audit pipeline running against warm device-resident pk planes and
    line tables — the steady-state production shape all three levers
    stack into, queued since PR 3. Asserts overlapped-vs-sequential
    verdict identity and the warm zero-G2 wire under composition, then
    reports the composed rate (the 05_resident/05_overlap/05_precomp
    probes emit this as the `composed_audit` workload)."""
    _setup_bench_env()

    import jax

    k_periods = int(os.environ.get("GETHSHARDING_BENCH_COMPOSED_K", "3"))
    notary, periods = build_audit_workload(k_periods)
    ps = periods[:k_periods]
    backend = notary.sig_backend

    # compile + cold-cache pass, then the overlap identity gate
    seq = {p: notary.audit_period(p) for p in ps}
    assert all(v is True for v in seq.values()), "audit inconsistent"
    ov = notary.audit_periods(ps, overlap=True)
    assert ov == seq, "overlapped verdicts must equal sequential"
    warm = dict(backend.last_wire or {})
    if warm.get("resident"):
        assert warm.get("g2_wire_bytes") == 0, (
            f"composed warm audits must ship zero G2 bytes: {warm}")

    iters = 2
    t0 = time.perf_counter()
    for _ in range(iters):
        res = notary.audit_periods(ps, overlap=True)
        assert all(res[p] is True for p in ps)
    wall = (time.perf_counter() - t0) / iters
    return {
        "platform": jax.devices()[0].platform,
        "k_periods": k_periods,
        "precomp": warm.get("precomp"),
        "resident": warm.get("resident"),
        "sig_rate": round(k_periods * SHARDS * COMMITTEE / wall, 1),
        "composed_wall_s": round(wall, 4),
        "g2_wire_bytes_warm": warm.get("g2_wire_bytes"),
        "pk_hit_rows_warm": warm.get("pk_hit_rows"),
        "knobs": _knob_snapshot(),
    }


# == serving-tier amortization (bench.py --serving) ========================


def measure_serving() -> dict:
    """M concurrent clients x small requests through the serving tier vs
    the same clients driving the backend directly — the dispatch-
    amortization claim measured, not asserted. Hermetic by default
    (python inner backend: the coalescing win is dispatch-count
    amortization, visible on any backend; set
    GETHSHARDING_BENCH_SERVING_BACKEND=jax on a live accelerator)."""
    import threading

    from gethsharding_tpu.crypto import secp256k1 as ecdsa
    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.serving import ServingConfig, ServingSigBackend
    from gethsharding_tpu.sigbackend import get_backend

    clients = int(os.environ.get("GETHSHARDING_BENCH_SERVING_CLIENTS", "32"))
    per_client = int(os.environ.get("GETHSHARDING_BENCH_SERVING_REQS", "16"))
    inner = get_backend(
        os.environ.get("GETHSHARDING_BENCH_SERVING_BACKEND", "python"))

    cases = []
    for i in range(clients * per_client):
        priv = int.from_bytes(keccak256(b"serve-%d" % i), "big") % ecdsa.N
        digest = keccak256(b"serve-msg-%d" % i)
        cases.append((digest, ecdsa.sign(digest, priv).to_bytes65(),
                      ecdsa.priv_to_address(priv)))

    def drive(recover) -> float:
        """Each client thread issues `per_client` single-item requests;
        returns wall seconds. Divergence is a hard failure."""
        errors: list = []

        def client(c: int) -> None:
            for r in range(per_client):
                digest, sig, want = cases[c * per_client + r]
                if recover([digest], [sig]) != [want]:
                    errors.append((c, r))

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, f"result divergence at {errors[:4]}"
        return time.perf_counter() - t0

    total = clients * per_client
    direct_s = drive(inner.ecrecover_addresses)

    serving = ServingSigBackend(inner, ServingConfig(
        max_batch=int(os.environ.get("GETHSHARDING_SERVING_MAX_BATCH",
                                     "128")),
        flush_us=float(os.environ.get("GETHSHARDING_SERVING_FLUSH_US",
                                      "2000"))))
    try:
        serving_s = drive(serving.ecrecover_addresses)
        dispatches = serving.dispatch_count
    finally:
        serving.close()

    return {
        "backend": inner.name,
        "clients": clients,
        "requests": total,
        "serving_rate": round(total / serving_s, 1),
        "direct_rate": round(total / direct_s, 1),
        "speedup": round(direct_s / serving_s, 3),
        "dispatches": dispatches,
        "coalesce_ratio": round(total / max(1, dispatches), 1),
    }


def measure_fleet() -> dict:
    """The fleet-serving acceptance run: 3 breaker-guarded serving
    replicas behind the shard router, driven by the traffic-model soak
    (diurnal curve, hot-shard skew, thundering-herd burst, mixed
    admission classes) while a seeded chaos schedule trips replica
    r0's breaker mid-soak. Asserts the ISSUE 8 closed-loop bar:

    - zero divergences (every result verified against the known
      signer) and zero hung clients — nothing lost or mis-answered;
    - r0 drained at least once and RE-ENTERED through half-open
      re-promotion;
    - interactive saw ZERO sheds and held its p99 SLO, while the
      catchup_replay flood was shed first (replica-level counters).

    Hermetic by default (python replicas — the SLO default is
    calibrated for scalar host crypto; tighten
    GETHSHARDING_FLEET_SLO_INTERACTIVE_MS on an accelerator)."""
    duration = float(os.environ.get("GETHSHARDING_BENCH_FLEET_S", "12"))
    slo_ms = float(os.environ.get(
        "GETHSHARDING_FLEET_SLO_INTERACTIVE_MS", "8000"))
    backend = os.environ.get("GETHSHARDING_BENCH_FLEET_BACKEND", "python")
    clients = int(os.environ.get("GETHSHARDING_BENCH_FLEET_CLIENTS", "16"))
    cmd = [sys.executable,
           os.path.join(REPO, "scripts", "serving_stress.py"),
           "--replicas", "3", "--clients", str(clients),
           "--duration", str(duration), "--backend", backend,
           "--max-batch", "16", "--queue-cap", "16", "--policy", "shed",
           "--classes", "interactive=8,bulk_audit=4,catchup_replay=4",
           "--chaos-trip", "10", "--hot-shard", "0.9",
           "--diurnal-s", str(max(4.0, duration / 2)),
           "--herd-at", str(duration / 3),
           "--slo-interactive-ms", str(slo_ms)]
    env = {**os.environ}
    if backend == "python":
        env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=duration * 20 + 120, cwd=REPO, env=env)
    lines = [line for line in proc.stdout.strip().splitlines()
             if line.startswith("{")]
    assert lines, f"no soak output (rc {proc.returncode}): {proc.stderr}"
    summary = json.loads(lines[-1])
    assert summary.get("summary") and summary.get("fleet"), summary
    # the closed-loop acceptance assertions (the soak gates these too —
    # rc != 0 means one of them failed inside the run)
    assert proc.returncode == 0, (summary, proc.stderr[-2000:])
    assert summary["divergences"] == 0, summary
    assert summary["hung_clients"] == 0, summary
    assert summary["interactive_shed"] == 0, summary
    assert summary["drain_events"] >= 1, summary
    assert summary["reentered"], summary
    assert summary["chaos_injected"] >= 3, summary
    sheds = summary["replica_shed_by_class"]
    caller = summary["caller_shed"]
    assert sheds["interactive"] == 0, summary
    assert sheds["catchup_replay"] + caller["catchup_replay"] > 0, (
        "the catchup flood never shed — the overload phase tested "
        "nothing", summary)
    assert summary["p99_ms"]["interactive"] <= slo_ms, summary

    # -- the SLO-layer overhead gate (the PR 2 tracer-budget shape) --------
    # the serving hot path now records one SLO event per request (and a
    # routed request records a second at the router); both together must
    # cost <2% of a serving request. Measured, not assumed: a real
    # serving request's latency vs the amortized cost of
    # SLOTracker.record on a warm tracker.
    from gethsharding_tpu.metrics import Registry
    from gethsharding_tpu.serving import ServingConfig, ServingSigBackend
    from gethsharding_tpu.sigbackend import PythonSigBackend
    from gethsharding_tpu.slo import SLOTracker

    serving = ServingSigBackend(PythonSigBackend(),
                                ServingConfig(flush_us=500.0),
                                registry=Registry())
    try:
        serving.ecrecover_addresses([], [])  # warm the threads
        n = 100
        t0 = time.perf_counter()
        for i in range(n):
            serving.ecrecover_addresses(
                [bytes([i % 251]) * 32], [b"\x00" * 65])
        per_request_s = (time.perf_counter() - t0) / n
    finally:
        serving.close()
    tracker = SLOTracker(registry=Registry())
    m = 20_000
    t0 = time.perf_counter()
    for _ in range(m):
        tracker.record("interactive", ok=True, latency_s=0.001)
    record_s = (time.perf_counter() - t0) / m
    slo_overhead_pct = 100.0 * 2 * record_s / per_request_s
    assert slo_overhead_pct < 2.0, (
        f"SLO layer overhead {slo_overhead_pct:.3f}% of a serving "
        f"request ({record_s * 1e6:.3f}us x2 vs "
        f"{per_request_s * 1e6:.1f}us) breaches the 2% budget")
    return {
        "replicas": 3,
        "clients": clients,
        "backend": backend,
        # the soak's replicas live in the child process; only the
        # scalar backend's platform is known from here
        "platform": "cpu" if backend == "python" else "not reported",
        "duration_s": duration,
        "p99_ms": summary["p99_ms"],
        "slo_ms": summary["slo_ms"],
        "done": summary["done"],
        "replica_shed_by_class": sheds,
        "caller_shed": caller,
        "drain_events": summary["drain_events"],
        "reentries": summary["reentries"],
        "chaos_injected": summary["chaos_injected"],
        "states": summary["states"],
        "slo_record_us": round(record_s * 1e6, 3),
        "slo_overhead_pct": round(slo_overhead_pct, 4),
    }


def measure_elastic() -> dict:
    """The elastic-fleet acceptance run (ISSUE 20): the cross-process
    closed-loop soak (scripts/serving_stress.py --elastic) — 2
    chain_server replicas behind TWO peered frontend processes,
    frontend A running the SLO-driven autoscaler, FrontendPool clients
    riding a 10x diurnal swing, frontend B killed -9 mid-swing.
    Asserts the closed loop END TO END:

    - zero incorrect verdicts and zero hung clients through membership
      churn, autoscale spawns/retires, and the frontend kill;
    - the actors failed over to the surviving frontend (pool failover
      counter >= 1 — the kill was actually felt and survived);
    - the autoscaler was observed acting in BOTH directions, countered
      via frontend A's shard_fleetStatus: scale-OUT at the peak
      (sustained federated queue depth) AND scale-IN at the trough;
    - interactive p99 held its SLO across the whole swing.

    The soak itself appends the `fleet_elastic` workload record to the
    perf ledger through `perfwatch.record_bench` (noise-aware gate);
    this wrapper re-emits the headline number with the bench stamp."""
    duration = float(os.environ.get("GETHSHARDING_BENCH_ELASTIC_S", "16"))
    slo_ms = float(os.environ.get(
        "GETHSHARDING_FLEET_SLO_INTERACTIVE_MS", "8000"))
    clients = int(os.environ.get("GETHSHARDING_BENCH_FLEET_CLIENTS", "16"))
    cmd = [sys.executable,
           os.path.join(REPO, "scripts", "serving_stress.py"),
           "--elastic", "--clients", str(clients),
           "--duration", str(duration),
           "--slo-interactive-ms", str(slo_ms)]
    env = {**os.environ}
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=duration * 20 + 180, cwd=REPO, env=env)
    lines = [line for line in proc.stdout.strip().splitlines()
             if line.startswith("{")]
    assert lines, f"no soak output (rc {proc.returncode}): {proc.stderr}"
    summary = json.loads(lines[-1])
    assert summary.get("summary") and summary.get("elastic"), summary
    assert proc.returncode == 0, (summary, proc.stderr[-2000:])
    assert summary["divergences"] == 0, summary
    assert summary["hung_clients"] == 0, summary
    assert summary["frontend_killed"], summary
    assert summary["failovers"] >= 1, summary
    assert summary["scale_out"] >= 1, summary
    assert summary["scale_in"] >= 1, summary
    assert summary["epoch"] >= 2, summary  # one add + one remove
    assert not summary["slo_breach"], summary
    summary["platform"] = "cpu (hermetic)"
    return summary


def measure_hedge() -> dict:
    """The request-hedging closed loop (ISSUE 15 acceptance): a
    3-replica fleet where replica r0's TRANSPORT is chaos-delayed 10x
    (seeded ``fleet.transport`` delay rule: ~8% of its calls stall
    0.12 s vs the ~ms scalar baseline), driven by keyed interactive
    traffic twice — hedging OFF, then hedging ON with the same seed.
    Asserts, not reports:

    - interactive p99 improves >= 2x with hedging on (the tail IS the
      delayed replica; the hedge answers from the next affinity
      replica after the floor delay);
    - wasted duplicate dispatches stay <= 15% of all dispatches
      (hedges fire on the delayed tail, not on every call);
    - zero divergences in either phase (every verdict checked against
      the known signer).
    """
    from gethsharding_tpu.crypto import secp256k1 as ecdsa
    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.fleet import FleetRouter, Replica
    from gethsharding_tpu.metrics import Registry
    from gethsharding_tpu.resilience.chaos import (ChaosSchedule,
                                                   TransportChaos)
    from gethsharding_tpu.sigbackend import PythonSigBackend

    calls = int(os.environ.get("GETHSHARDING_BENCH_HEDGE_CALLS", "400"))
    delay_s = float(os.environ.get("GETHSHARDING_BENCH_HEDGE_DELAY_S",
                                   "0.12"))
    rate = float(os.environ.get("GETHSHARDING_BENCH_HEDGE_RATE", "0.08"))
    # the fleet-wide flag may be exported as 0 (hedging off in prod);
    # the CLOSED LOOP always hedges — a non-positive ambient value
    # falls back to the bench default instead of un-arming the gate
    hedge_ms = float(os.environ.get("GETHSHARDING_FLEET_HEDGE_MS")
                     or 0) or 15.0
    if hedge_ms <= 0:
        hedge_ms = 15.0
    cases = []
    for i in range(64):
        priv = int.from_bytes(keccak256(b"hedge-%d" % i), "big") % ecdsa.N
        digest = keccak256(b"hedge-msg-%d" % i)
        cases.append((digest, ecdsa.sign(digest, priv).to_bytes65(),
                      ecdsa.priv_to_address(priv)))

    def run_phase(hedge_on: bool) -> dict:
        registry = Registry()
        schedule = ChaosSchedule(
            seed=29, rules={"fleet.transport": rate},
            modes={"fleet.transport": "delay"}, delay_s=delay_s)
        replicas = [
            Replica("r0", TransportChaos(PythonSigBackend(), schedule),
                    probe=None, registry=registry),
            Replica("r1", PythonSigBackend(), probe=None,
                    registry=registry),
            Replica("r2", PythonSigBackend(), probe=None,
                    registry=registry),
        ]
        router = FleetRouter(replicas, health_interval_s=0.0,
                             hedge_ms=hedge_ms if hedge_on else 0,
                             registry=registry)
        lat, divergences = [], 0
        try:
            for i in range(calls):
                digest, sig, want = cases[i % len(cases)]
                t0 = time.perf_counter()
                got = router.call("ecrecover_addresses", [digest], [sig],
                                  affinity=f"shard-{i % 64}")
                lat.append(time.perf_counter() - t0)
                if got != [want]:
                    divergences += 1
            time.sleep(delay_s + 0.2)  # let hedge losers finish
        finally:
            router.close()
        lat.sort()
        stats = router.hedge_stats()
        return {
            "p50_ms": round(lat[len(lat) // 2] * 1e3, 2),
            "p99_ms": round(lat[int(0.99 * (len(lat) - 1))] * 1e3, 2),
            "divergences": divergences,
            "hedge": stats,
            "dispatches": calls + stats["issued"],
        }

    base = run_phase(hedge_on=False)
    hedged = run_phase(hedge_on=True)
    assert base["divergences"] == 0 and hedged["divergences"] == 0, (
        base, hedged)
    improvement = base["p99_ms"] / max(hedged["p99_ms"], 1e-9)
    assert improvement >= 2.0, (
        f"hedging bought only {improvement:.2f}x on interactive p99 "
        f"({base['p99_ms']} ms -> {hedged['p99_ms']} ms) — the "
        f"acceptance bar is 2x", base, hedged)
    wasted_pct = 100.0 * hedged["hedge"]["wasted"] / hedged["dispatches"]
    assert wasted_pct <= 15.0, (
        f"hedging wasted {wasted_pct:.1f}% of dispatches "
        f"(bar: <=15%)", hedged)
    assert hedged["hedge"]["issued"] > 0, (
        "the delayed tail never triggered a hedge — the phase tested "
        "nothing", hedged)
    return {
        "calls": calls,
        "delay_s": delay_s,
        "delay_rate": rate,
        "hedge_ms": hedge_ms,
        "p99_ms_no_hedge": base["p99_ms"],
        "p99_ms_hedged": hedged["p99_ms"],
        "p50_ms_hedged": hedged["p50_ms"],
        "improvement": round(improvement, 2),
        "hedges_issued": hedged["hedge"]["issued"],
        "hedges_won": hedged["hedge"]["won"],
        "hedges_wasted": hedged["hedge"]["wasted"],
        "wasted_pct": round(wasted_pct, 2),
    }


def measure_partition() -> dict:
    """The partition/kill soak (ISSUE 15 acceptance): mixed interactive
    traffic over a hedged 3-replica fleet while, mid-soak, replica r0
    is KILLED (its serving tier closed — every later call fails
    typed) and replica r1 is PARTITIONED for a seeded window
    (``fleet.transport`` partition rule: the wire raises the retryable
    transport fault, the router's consecutive-failure path trips it,
    and it re-enters after the window through the ordinary
    cooldown+health path). Asserted, not reported: ZERO incorrect
    verdicts, every caller-visible failure TYPED
    (shed/drain/deadline), and the partitioned replica re-entered."""
    import threading

    from gethsharding_tpu.crypto import secp256k1 as ecdsa
    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.fleet import (AllReplicasDraining, FleetRouter,
                                        Replica)
    from gethsharding_tpu.metrics import Registry
    from gethsharding_tpu.resilience.chaos import (ChaosSchedule,
                                                   TransportChaos)
    from gethsharding_tpu.resilience.errors import DeadlineExceeded
    from gethsharding_tpu.serving import (ServingConfig,
                                          ServingOverloadError,
                                          ServingSigBackend)
    from gethsharding_tpu.sigbackend import PythonSigBackend

    registry = Registry()
    # r1's partition window: wire calls 30..110 are refused (the
    # schedule is per-seam-call, so the window length covers the soak's
    # middle even with retries consuming slots)
    schedule = ChaosSchedule(
        seed=31, rules={"fleet.transport": lambda idx: 30 <= idx < 110},
        modes={"fleet.transport": "partition"})
    serving0 = ServingSigBackend(PythonSigBackend(),
                                 ServingConfig(flush_us=200),
                                 registry=registry)
    replicas = [
        Replica("r0", serving0, probe=None, registry=registry),
        Replica("r1", TransportChaos(PythonSigBackend(), schedule),
                probe=None, registry=registry,
                trip_cooldown_s=0.3),
        Replica("r2", PythonSigBackend(), probe=None, registry=registry),
    ]
    router = FleetRouter(replicas, health_interval_s=0.05, hedge_ms=10,
                         registry=registry)
    cases = []
    for i in range(32):
        priv = int.from_bytes(keccak256(b"part-%d" % i), "big") % ecdsa.N
        digest = keccak256(b"part-msg-%d" % i)
        cases.append((digest, ecdsa.sign(digest, priv).to_bytes65(),
                      ecdsa.priv_to_address(priv)))
    typed = (ServingOverloadError, AllReplicasDraining, DeadlineExceeded)
    divergences: list = []
    untyped: list = []
    typed_losses = {"shed": 0, "drain": 0, "deadline": 0}
    completed = [0]
    rounds = int(os.environ.get("GETHSHARDING_BENCH_PARTITION_ROUNDS",
                                "50"))
    kill_at = rounds // 3

    def client(c: int) -> None:
        for r in range(rounds):
            digest, sig, want = cases[(c * rounds + r) % len(cases)]
            try:
                got = router.call("ecrecover_addresses", [digest], [sig],
                                  affinity=f"shard-{(c + r) % 24}")
            except typed as exc:
                if isinstance(exc, AllReplicasDraining):
                    typed_losses["drain"] += 1
                elif isinstance(exc, DeadlineExceeded):
                    typed_losses["deadline"] += 1
                else:
                    typed_losses["shed"] += 1
                continue
            except Exception as exc:  # noqa: BLE001 - the gate itself
                untyped.append(repr(exc))
                continue
            completed[0] += 1
            if got != [want]:
                divergences.append((c, r, got))
            time.sleep(0.004)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(4)]
    for t in threads:
        t.start()
    # mid-soak kill: r0's serving tier closes under traffic — queued
    # futures fail typed, later calls refuse typed, the router retries
    # the survivors
    time.sleep(kill_at * 0.004 * 2)
    serving0.close()
    for t in threads:
        t.join(timeout=120)
    hung = [t for t in threads if t.is_alive()]
    # the partitioned replica's window is over: it re-enters through
    # cooldown + the background sweep
    deadline = time.monotonic() + 10
    while replicas[1].state != "healthy" and time.monotonic() < deadline:
        router.refresh(force=True)
        time.sleep(0.05)
    stats = router.hedge_stats()
    states = router.states()
    router.close()
    assert not hung, "hung soak client"
    assert divergences == [], divergences[:3]
    assert untyped == [], untyped[:5]
    assert completed[0] > 0
    assert replicas[1].state == "healthy", states
    assert replicas[1].reentries >= 1, states
    return {
        "rounds": rounds,
        "clients": 4,
        "completed": completed[0],
        "typed_losses": typed_losses,
        "untyped_losses": 0,
        "divergences": 0,
        "r1_trips_reentries": replicas[1].reentries,
        "hedge": stats,
        "states": {name: s["state"] for name, s in states.items()},
    }


def measure_chaos() -> dict:
    """Failover availability under a seeded chaos schedule: N ecrecover
    calls through `FailoverSigBackend` while the primary backend is hit
    by deterministic injected faults. The metric is the fraction of
    calls answered CORRECTLY (fallback-covered faults included) — the
    paper's always-vote contract, measured. Also reports the breaker's
    full cycle (trips, probes, re-close) under the schedule. Hermetic
    by default (python primary); GETHSHARDING_BENCH_CHAOS_BACKEND=jax
    runs the real device path on an accelerator (the 06_failover
    probe).

    GETHSHARDING_CHAOS_MODE=corrupt switches the schedule to SILENT
    corruption (wrong answers, no exceptions) with the soundness
    spot-checker (rate GETHSHARDING_SOUNDNESS_RATE) composed inside
    the failover slot; the report's detected-vs-undetected corruption
    counts say how much of the injected corruption the audit caught
    (detected corruption is served from the fallback and stays
    correct; undetected corruption is a wrong answer)."""
    from gethsharding_tpu.crypto import secp256k1 as ecdsa
    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.metrics import Registry
    from gethsharding_tpu.resilience.breaker import (
        CLOSED, CircuitBreaker, FailoverSigBackend)
    from gethsharding_tpu.resilience.chaos import (ChaosSchedule,
                                                   ChaosSigBackend)
    from gethsharding_tpu.sigbackend import PythonSigBackend, get_backend

    seed = int(os.environ.get("GETHSHARDING_CHAOS_SEED", "42"))
    rate = float(os.environ.get("GETHSHARDING_CHAOS_RATE", "0.3"))
    calls = int(os.environ.get("GETHSHARDING_BENCH_CHAOS_CALLS", "60"))
    rows = int(os.environ.get("GETHSHARDING_BENCH_CHAOS_ROWS", "8"))
    primary_name = os.environ.get("GETHSHARDING_BENCH_CHAOS_BACKEND",
                                  "python")
    mode = os.environ.get("GETHSHARDING_CHAOS_MODE", "fault")
    import random

    # faults only for the first 2/3 of the run: the tail is the recovery
    # window where the breaker must probe its way back to closed
    fault_calls = (calls * 2) // 3

    def fault_rule(idx: int) -> bool:
        return (idx < fault_calls
                and random.Random(f"{seed}:bench:{idx}").random() < rate)

    schedule = ChaosSchedule(
        seed=seed, rules={"backend.ecrecover_addresses": fault_rule},
        modes=({"backend.ecrecover_addresses": "corrupt"}
               if mode == "corrupt" else None))
    registry = Registry()
    breaker = CircuitBreaker(name="bench", fault_threshold=2,
                             reset_s=0.002, registry=registry)
    primary = ChaosSigBackend(get_backend(primary_name), schedule)
    if mode == "corrupt":
        # silent corruption is invisible to the breaker's exception
        # path: only the spot-checker can turn it into a fault
        from gethsharding_tpu.resilience.soundness import (
            SpotCheckSigBackend)

        primary = SpotCheckSigBackend(primary, registry=registry)
    backend = FailoverSigBackend(
        primary, PythonSigBackend(), breaker=breaker, registry=registry)

    batches = []
    for b in range(calls):
        digests, sigs, wants = [], [], []
        for r in range(rows):
            priv = int.from_bytes(
                keccak256(b"chaos-%d-%d" % (b, r)), "big") % ecdsa.N
            digest = keccak256(b"chaos-msg-%d-%d" % (b, r))
            digests.append(digest)
            sigs.append(ecdsa.sign(digest, priv).to_bytes65())
            wants.append(ecdsa.priv_to_address(priv))
        batches.append((digests, sigs, wants))

    correct = answered = 0
    t0 = time.perf_counter()
    for digests, sigs, wants in batches:
        try:
            got = backend.ecrecover_addresses(digests, sigs)
            answered += 1
            correct += int(got == wants)
        except Exception:  # noqa: BLE001 - an escape IS the finding
            pass
        time.sleep(0.004)  # let open-state cooldowns elapse
    wall_s = time.perf_counter() - t0

    def count(metric: str) -> int:
        return registry.counter(f"resilience/breaker/bench/{metric}").value

    injected = schedule.injected.get("backend.ecrecover_addresses", 0)
    # corrupt-mode accounting: a corruption the spot-checker caught
    # became a SoundnessViolation (served correct from the fallback);
    # one it missed is a silently wrong answer
    detected = registry.counter(
        "resilience/soundness/ecrecover_addresses/mismatches").value
    undetected = answered - correct if mode == "corrupt" else 0
    return {
        "primary": primary_name,
        "seed": seed,
        "rate": rate,
        "mode": mode,
        "calls": calls,
        "rows": rows,
        "chaos_availability": round(correct / calls, 4),
        "answered": answered,
        "injected_faults": injected if mode != "corrupt" else 0,
        "corruptions_injected": injected if mode == "corrupt" else 0,
        "corruptions_detected": detected,
        "corruptions_undetected": undetected,
        "breaker_trips": count("trips"),
        "breaker_probes": count("probes"),
        "breaker_closes": count("closes"),
        "fallback_calls": count("fallback_calls"),
        "breaker_reclosed": breaker.state == CLOSED,
        "wall_s": round(wall_s, 3),
        "platform": _chaos_platform(primary_name),
    }


def _chaos_platform(primary_name: str) -> str:
    if "jax" not in primary_name:
        return "host"
    import jax

    return jax.devices()[0].platform


def measure_soundness() -> dict:
    """The continuous soundness audit's two acceptance numbers in one
    run (bench.py --soundness):

    1. **Overhead** at the DEFAULT sample rate: the audit work per
       dispatch (always-on invariant sweep + rate-amortized sampled
       scalar re-verification) measured directly against the cost of a
       real-signature ecrecover dispatch — asserted <2%, the same
       budget-guard shape as the tracing and closed-breaker guards.
    2. **Closed-loop detection**: an every-dispatch silent corruptor
       (chaos mode=corrupt — wrong answers, no exceptions) must trip
       the failover breaker within the dispatch budget
       `dispatches_to_detect` predicts at 99.9% confidence.

    Hermetic by default (python primary);
    GETHSHARDING_BENCH_SOUNDNESS_BACKEND=jax times the real device
    dispatch (the 08_soundness probe)."""
    from gethsharding_tpu.crypto import secp256k1 as ecdsa
    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.metrics import Registry
    from gethsharding_tpu.resilience.breaker import (
        OPEN, CircuitBreaker, FailoverSigBackend)
    from gethsharding_tpu.resilience.chaos import (ChaosSchedule,
                                                   ChaosSigBackend)
    from gethsharding_tpu.resilience.soundness import (
        DEFAULT_RATE, DEFAULT_ROWS, SpotCheckSigBackend,
        detection_probability, dispatches_to_detect, soundness_table)
    from gethsharding_tpu.sigbackend import PythonSigBackend, get_backend

    seed = int(os.environ.get("GETHSHARDING_SOUNDNESS_SEED", "0"))
    rows = int(os.environ.get("GETHSHARDING_BENCH_SOUNDNESS_ROWS", "32"))
    primary_name = os.environ.get("GETHSHARDING_BENCH_SOUNDNESS_BACKEND",
                                  "python")
    primary = get_backend(primary_name)

    # -- part 1: audit overhead against a real-signature dispatch ----------
    digests, sigs = [], []
    for r in range(rows):
        priv = int.from_bytes(
            keccak256(b"soundness-%d" % r), "big") % ecdsa.N
        digest = keccak256(b"soundness-msg-%d" % r)
        digests.append(digest)
        sigs.append(ecdsa.sign(digest, priv).to_bytes65())
    cols = (digests, sigs)

    reps = 2 if primary_name == "python" else 8
    t0 = time.perf_counter()
    for _ in range(reps):
        out = primary.ecrecover_addresses(digests, sigs)
    per_dispatch_s = (time.perf_counter() - t0) / reps

    spot = SpotCheckSigBackend(primary, rate=DEFAULT_RATE,
                               rows=DEFAULT_ROWS, seed=seed,
                               registry=Registry())
    m = 50
    t0 = time.perf_counter()
    for _ in range(m):
        spot._check_invariants("ecrecover_addresses", cols, out)
        spot._tick("ecrecover_addresses")
    invariant_s = (time.perf_counter() - t0) / m
    k = 3
    t0 = time.perf_counter()
    for i in range(k):
        spot._spot_check("ecrecover_addresses", cols, out, idx=i)
    spotcheck_s = (time.perf_counter() - t0) / k
    # what one dispatch pays on average: the always-on sweep plus the
    # rate-amortized sampled re-verification
    audit_s = invariant_s + DEFAULT_RATE * spotcheck_s
    overhead_pct = 100.0 * audit_s / per_dispatch_s
    assert overhead_pct < 2.0, (
        f"soundness audit overhead {overhead_pct:.3f}% of a "
        f"{rows}-row dispatch ({audit_s * 1e6:.1f}us vs "
        f"{per_dispatch_s * 1e6:.1f}us) breaches the 2% budget")

    # -- part 2: closed-loop detection within the predicted budget ---------
    # an ambient GETHSHARDING_SOUNDNESS_RATE=0 (the node's off switch)
    # must not crash the closed loop — detection at rate 0 has no
    # budget, so the run falls back to the demonstration rate
    check_rate = float(os.environ.get("GETHSHARDING_SOUNDNESS_RATE",
                                      "0.25") or 0)
    if check_rate <= 0:
        check_rate = 0.25
    chaos_rows = 8
    budget = dispatches_to_detect(check_rate, DEFAULT_ROWS, chaos_rows,
                                  corrupt_rows=1, confidence=0.999)
    schedule = ChaosSchedule(
        seed=seed, rules={"backend.ecrecover_addresses": True},
        modes={"backend.ecrecover_addresses": "corrupt"})
    registry = Registry()
    breaker = CircuitBreaker(name="soundness", fault_threshold=1,
                             reset_s=60.0, registry=registry)
    backend = FailoverSigBackend(
        SpotCheckSigBackend(ChaosSigBackend(PythonSigBackend(), schedule),
                            rate=check_rate, rows=DEFAULT_ROWS, seed=seed,
                            registry=registry),
        PythonSigBackend(), breaker=breaker, registry=registry)
    garbage = ([b"\x11" * 32] * chaos_rows, [b"\x22" * 65] * chaos_rows)
    dispatches_to_trip = None
    for i in range(budget):
        backend.ecrecover_addresses(*garbage)
        if breaker.state == OPEN:
            dispatches_to_trip = i + 1
            break
    detected = dispatches_to_trip is not None
    assert detected, (
        f"silent corruption NOT detected within the predicted "
        f"{budget}-dispatch budget (rate {check_rate}, "
        f"{DEFAULT_ROWS}/{chaos_rows} rows)")

    return {
        "primary": primary_name,
        "rows": rows,
        "overhead_pct": round(overhead_pct, 4),
        "default_rate": DEFAULT_RATE,
        "rows_per_check": DEFAULT_ROWS,
        "per_dispatch_us": round(per_dispatch_s * 1e6, 1),
        "audit_us_per_dispatch": round(audit_s * 1e6, 2),
        "invariant_us": round(invariant_s * 1e6, 2),
        "spot_check_us": round(spotcheck_s * 1e6, 1),
        "detection_rate": check_rate,
        "dispatches_to_trip": dispatches_to_trip,
        "predicted_budget_p999": budget,
        "p_detect_per_dispatch": round(detection_probability(
            check_rate, DEFAULT_ROWS, chaos_rows), 4),
        "soundness_mismatches": registry.counter(
            "resilience/soundness/ecrecover_addresses/mismatches").value,
        "soundness_table_64": soundness_table(64, DEFAULT_ROWS),
        "platform": _chaos_platform(primary_name),
    }


# == data-availability sampling (bench.py --das) ===========================


def measure_das() -> dict:
    """Full-fetch vs sampled availability: bytes per collation, plus
    batched sample-verify throughput.

    Part 1 is the END-TO-END acceptance run: a proposer publishes
    erasure-extended bodies, a notary in sampled DA mode votes across
    several periods over a live shardp2p hub, and the harness asserts
    (a) not one CollationBodyRequest left the notary and (b) fetched
    bytes per collation stay within k·chunk_size + proof overhead —
    against the full-fetch baseline of body_size bytes per collation.

    Part 2 measures `das_verify_samples` rows/sec: the scalar python
    reference vs the batched backend (GETHSHARDING_BENCH_DAS_BACKEND,
    default jax), verdict-checked bit-for-bit. Hermetic on CPU; the
    07_das probe runs the same thing against the real chip."""
    import random as _random

    from gethsharding_tpu.actors.notary import Notary
    from gethsharding_tpu.actors.proposer import create_collation
    from gethsharding_tpu.core.shard import Shard
    from gethsharding_tpu.core.types import Transaction
    from gethsharding_tpu.das.erasure import DAS_CHUNK_SIZE, extend_body
    from gethsharding_tpu.das.proofs import (MAX_PROOF_DEPTH, chunk_leaf,
                                             merkle_levels, merkle_proof)
    from gethsharding_tpu.das.sampler import detection_probability
    from gethsharding_tpu.das.service import DASService
    from gethsharding_tpu.db.kv import MemoryKV
    from gethsharding_tpu.mainchain.client import SMCClient
    from gethsharding_tpu.p2p.messages import CollationBodyRequest
    from gethsharding_tpu.p2p.service import Hub, P2PServer
    from gethsharding_tpu.params import Config, ETHER
    from gethsharding_tpu.sigbackend import get_backend
    from gethsharding_tpu.smc.chain import SimulatedMainchain

    body_size = int(os.environ.get("GETHSHARDING_BENCH_DAS_BODY",
                                   str(256 * 1024)))
    k_samples = int(os.environ.get("GETHSHARDING_BENCH_DAS_SAMPLES", "16"))
    n_periods = int(os.environ.get("GETHSHARDING_BENCH_DAS_PERIODS", "3"))
    backend_name = os.environ.get("GETHSHARDING_BENCH_DAS_BACKEND", "jax")

    # -- part 1: the sampled-notary acceptance run -------------------------
    config = Config(quorum_size=1, period_length=4)
    chain = SimulatedMainchain(config=config)
    prop_client = SMCClient(backend=chain, config=config)
    not_client = SMCClient(backend=chain, config=config)
    chain.fund(prop_client.account(), 2000 * ETHER)
    chain.fund(not_client.account(), 2000 * ETHER)
    hub = Hub()
    watch = P2PServer(hub)
    watch.start()  # must be hub-attached or broadcasts never reach it
    body_watch = watch.subscribe(CollationBodyRequest)
    svc_prop = DASService(client=prop_client, p2p=P2PServer(hub),
                          samples=k_samples)
    svc_not = DASService(client=not_client, p2p=P2PServer(hub),
                         samples=k_samples)
    svc_prop.start()
    svc_not.start()
    notary = Notary(client=not_client, shard=Shard(0, MemoryKV()),
                    p2p=svc_not.p2p, config=config, deposit_flag=True,
                    all_shards=False, sig_backend=get_backend("python"),
                    das=svc_not, da_mode="sampled")
    notary.start()
    chain.fast_forward(1)
    rng = _random.Random(1)
    try:
        for _ in range(n_periods):
            period = chain.current_period()
            collation = create_collation(
                prop_client, 0, period,
                [Transaction(nonce=period,
                             payload=bytes(rng.randrange(256)
                                           for _ in range(body_size)))])
            svc_prop.publish(0, period, collation.header.chunk_root,
                             collation.body)
            prop_client.add_header(0, period,
                                   collation.header.chunk_root,
                                   collation.header.proposer_signature)
            chain.commit()
            notary.notarize_collations(head=chain.block_number)
            while chain.current_period() == period:
                chain.commit()
        assert notary.votes_submitted == n_periods, notary.errors
        assert body_watch.try_get() is None, \
            "a CollationBodyRequest left the sampled notary"
        sampled_bytes = svc_not.bytes_fetched / n_periods
        budget = k_samples * (DAS_CHUNK_SIZE + 32 * MAX_PROOF_DEPTH + 40)
        assert sampled_bytes <= budget, (sampled_bytes, budget)
    finally:
        notary.stop()
        svc_prop.stop()
        svc_not.stop()
        watch.stop()

    # -- part 2: batched verify throughput ---------------------------------
    xb = extend_body(bytes(rng.randrange(256)
                           for _ in range(body_size)), 0.5)
    levels = merkle_levels([chunk_leaf(c) for c in xb.chunks])
    das_root = levels[-1][0]
    rows = int(os.environ.get("GETHSHARDING_BENCH_DAS_ROWS", "128"))
    idx = [rng.randrange(xb.n) for _ in range(rows)]
    chunks = [xb.chunks[i] for i in idx]
    prfs = [merkle_proof(levels, i) for i in idx]
    roots = [das_root] * rows
    scalar = get_backend("python")
    batched = get_backend(backend_name)
    want = scalar.das_verify_samples(chunks, idx, prfs, roots)
    assert all(want)
    got = batched.das_verify_samples(chunks, idx, prfs, roots)  # compile
    assert got == want, "batched verdicts diverge from scalar"
    iters = 3
    t0 = time.perf_counter()
    for _ in range(iters):
        batched.das_verify_samples(chunks, idx, prfs, roots)
    batched_s = (time.perf_counter() - t0) / iters
    t0 = time.perf_counter()
    scalar.das_verify_samples(chunks, idx, prfs, roots)
    scalar_s = time.perf_counter() - t0
    ledger = getattr(batched, "last_wire", None) or {}

    import jax

    return {
        "platform": jax.devices()[0].platform,
        "body_bytes": body_size,
        "k_samples": k_samples,
        "periods": n_periods,
        "votes": n_periods,
        "full_fetch_bytes_per_collation": body_size,
        "sampled_bytes_per_collation": round(sampled_bytes, 1),
        "bytes_ratio": round(sampled_bytes / body_size, 4),
        "sample_budget_bytes": budget,
        "detection_probability": round(
            detection_probability(k_samples, xb.n, xb.k), 6),
        "verify_rows": rows,
        "verify_backend": backend_name,
        "verify_rows_per_sec": round(rows / batched_s, 1),
        "scalar_rows_per_sec": round(rows / scalar_s, 1),
        "verify_speedup": round(scalar_s / batched_s, 3),
        "sample_wire_bytes_per_dispatch": ledger.get("sample_wire_bytes"),
    }


# == polynomial-multiproof DAS (bench.py --das-poly) =======================


def measure_das_poly() -> dict:
    """Constant-size multiproofs vs merkle paths: proof bytes per
    sampled collation, plus batched multiproof-verify throughput.

    Part 1 is the proof-size acceptance check: at the default sampling
    shape (k sampled chunks per collation) the polynomial multiproof
    is ONE 64-byte G1 point where the merkle mode ships k sibling
    paths — the run asserts the ≥5× byte cut the scheme exists for,
    and that the proof stays 64 bytes as k grows.

    Part 2 measures `das_verify_multiproofs` rows/sec: the scalar PCS
    reference (one two-pair pairing per row, host python) vs the
    batched backend (GETHSHARDING_BENCH_DAS_BACKEND, default jax)
    folding every row into one fixed-shape pairing dispatch,
    verdict-checked bit-for-bit. Hermetic on CPU."""
    import random as _random

    from gethsharding_tpu.das import pcs
    from gethsharding_tpu.das.erasure import extend_body
    from gethsharding_tpu.das.sampler import proof_bytes, sample_indices
    from gethsharding_tpu.sigbackend import get_backend

    body_size = int(os.environ.get("GETHSHARDING_BENCH_DAS_BODY",
                                   str(256 * 1024)))
    k_samples = int(os.environ.get("GETHSHARDING_BENCH_DAS_SAMPLES", "16"))
    rows = int(os.environ.get("GETHSHARDING_BENCH_DAS_POLY_ROWS", "6"))
    backend_name = os.environ.get("GETHSHARDING_BENCH_DAS_BACKEND", "jax")
    rng = _random.Random(1)

    # -- part 1: proof bytes per sampled collation -------------------------
    merkle_bytes = proof_bytes(k_samples, "merkle")
    poly_bytes = proof_bytes(k_samples, "poly")
    xb = extend_body(bytes(rng.randrange(256)
                           for _ in range(body_size)), 0.5)
    values = [pcs.chunk_value(c) for c in xb.chunks]
    indices = sample_indices(rng.randbytes(32), k_samples, xb.n)
    proof, _evals = pcs.open_multi(values, indices)
    assert len(pcs.g1_to_bytes(proof)) == poly_bytes == 64
    assert merkle_bytes >= 5 * poly_bytes, (merkle_bytes, poly_bytes)
    # constant in k: doubling the sample count moves the merkle cost,
    # not the poly cost
    wide = sample_indices(rng.randbytes(32), 2 * k_samples, xb.n)
    wide_proof, _ = pcs.open_multi(values, wide)
    assert len(pcs.g1_to_bytes(wide_proof)) == poly_bytes

    # -- part 2: batched verify throughput ---------------------------------
    commitments, index_rows, eval_rows, proofs, ns = [], [], [], [], []
    for row in range(rows):
        row_values = [rng.randrange(pcs.N) for _ in range(xb.n)]
        row_indices = sample_indices(rng.randbytes(32), k_samples, xb.n)
        row_proof, row_evals = pcs.open_multi(row_values, row_indices)
        commitments.append(pcs.g1_to_bytes(pcs.commit(row_values)))
        index_rows.append(row_indices)
        eval_rows.append(row_evals)
        proofs.append(pcs.g1_to_bytes(row_proof))
        ns.append(xb.n)
    scalar = get_backend("python")
    batched = get_backend(backend_name)
    t0 = time.perf_counter()
    want = scalar.das_verify_multiproofs(commitments, index_rows,
                                         eval_rows, proofs, ns)
    scalar_s = time.perf_counter() - t0
    assert all(want)
    got = batched.das_verify_multiproofs(commitments, index_rows,
                                         eval_rows, proofs, ns)  # compile
    assert got == want, "batched multiproof verdicts diverge from scalar"
    iters = 3
    t0 = time.perf_counter()
    for _ in range(iters):
        batched.das_verify_multiproofs(commitments, index_rows,
                                       eval_rows, proofs, ns)
    batched_s = (time.perf_counter() - t0) / iters
    ledger = getattr(batched, "last_wire", None) or {}

    import jax

    return {
        "platform": jax.devices()[0].platform,
        "body_bytes": body_size,
        "k_samples": k_samples,
        "n_chunks": xb.n,
        "merkle_proof_bytes_per_collation": merkle_bytes,
        "poly_proof_bytes_per_collation": poly_bytes,
        "proof_bytes_cut": round(merkle_bytes / poly_bytes, 2),
        "verify_rows": rows,
        "verify_backend": backend_name,
        "verify_rows_per_sec": round(rows / batched_s, 2),
        "scalar_rows_per_sec": round(rows / scalar_s, 2),
        "verify_speedup": round(scalar_s / batched_s, 3),
        "wire_bytes_per_dispatch": ledger.get("wire_bytes"),
    }


# == perfwatch closed-loop acceptance (bench.py --perfwatch) ===============


def measure_perfwatch() -> dict:
    """The measurement substrate's own acceptance run, closed-loop:

    1. **Gate trips on a real slowdown.** Seed a fresh ledger with
       clean CPU-quick micro-suite runs, assert the gate passes, inject
       a 1.3x slowdown into one registered microbenchmark and assert
       `--check` flags exactly that workload, then assert a clean rerun
       passes again (the injected record does not poison the median).
    2. **The timer cannot be lied to.** A simulated no-op
       `block_until_ready` (a block that does not wait) must increment
       `perfwatch/timer_suspect` and flag the enclosing ledger record
       invalid.
    3. **The black box is complete.** A chaos-injected dispatch hang
       under the serving watchdog must produce a flight-recorder bundle
       containing the event ring (with the watchdog_timeout and
       chaos_decision events), the finished-span ring, a metrics
       snapshot, and the ledger tail.
    4. **It all stays cheap.** DeviceTimer + recorder ring appends per
       dispatch are measured against a real serving request and
       asserted <2% — the same budget bar as the tracing and SLO
       layers."""
    import tempfile
    import threading

    import numpy as _np

    from gethsharding_tpu import metrics as _metrics
    from gethsharding_tpu import perfwatch
    from gethsharding_tpu.perfwatch import gate as pgate
    from gethsharding_tpu.perfwatch import registry as pregistry
    from gethsharding_tpu.perfwatch.ledger import Ledger
    from gethsharding_tpu.perfwatch.recorder import RECORDER
    from gethsharding_tpu.perfwatch.timer import DeviceTimer

    out: dict = {}
    tmp = tempfile.mkdtemp(prefix="bench_perfwatch_")
    ledger = Ledger(os.path.join(tmp, "ledger.jsonl"))

    # -- part 1: the regression gate, tripped by an honest 1.3x ------------
    # the drill lane is the deterministic clock-spin reference bench:
    # the REAL workload benches drift ~20% with host load on a shared
    # box (their gating belongs to a quiet CI lane, with the band
    # doing the noise absorption), but the acceptance contract here —
    # "1.3x trips, clean reruns do not" — must hold on ANY machine,
    # so it is asserted on the bench whose wall the clock controls
    target = "clock_spin_5ms"
    lane = [f"micro/{target}"]
    clean_runs = 4
    for _ in range(clean_runs):
        pregistry.run_suite(ledger=ledger, quick=True, inject={})
    full = pgate.check(ledger)  # the whole-suite face, reported below
    clean = pgate.check(ledger, workloads=lane)
    assert not clean.failed, [vars(v) for v in clean.regressions]
    pregistry.run_suite(ledger=ledger, quick=True,
                        inject={target: 1.3})
    tripped = pgate.check(ledger, workloads=lane)
    flagged = {v.workload for v in tripped.regressions}
    assert tripped.failed and f"micro/{target}" in flagged, (
        f"injected 1.3x slowdown on {target} did not trip the gate: "
        f"{[vars(v) for v in tripped.verdicts]}")
    pregistry.run_suite(ledger=ledger, quick=True, inject={})
    healed = pgate.check(ledger, workloads=lane)
    assert not healed.failed, (
        "clean rerun after the injected record still trips",
        [vars(v) for v in healed.regressions])
    out["gate_clean_runs"] = clean_runs
    out["gate_metrics_checked"] = len(full.verdicts)
    out["gate_tripped_on"] = sorted(flagged)

    # -- part 2: the simulated no-op block_until_ready ---------------------
    class _NoopBlockValue:
        """block_until_ready returns instantly; the REAL pull takes the
        dispatch latency — a block that does not wait."""

        def block_until_ready(self):
            return self

        def __array__(self, dtype=None, copy=None):
            time.sleep(0.3)  # the "real" dispatch the block hid —
            # above the 0.25 s suspect floor, like the r4 0.455 s case
            return _np.zeros(4, dtype=dtype or _np.int32)

    suspects_before = perfwatch.suspect_count()
    dt = DeviceTimer("bench/suspect_demo")
    dt.dispatched()
    dt.pull(_NoopBlockValue())
    dt.done()
    assert dt.suspect, "no-op block_until_ready went undetected"
    assert perfwatch.suspect_count() == suspects_before + 1
    # ... and a record taken over the suspect window is stamped invalid
    rec = perfwatch.record_bench(
        metric="suspect_demo", value=dt.device_s, unit="s", extra={},
        suspects=perfwatch.suspect_count() - suspects_before,
        ledger=ledger)
    assert rec["valid"] is False, rec
    out["timer_suspects"] = perfwatch.suspect_count() - suspects_before
    out["suspect_record_valid"] = rec["valid"]

    # -- part 3: chaos hang -> watchdog -> complete black-box bundle -------
    from gethsharding_tpu.resilience.chaos import (ChaosSchedule,
                                                   ChaosSigBackend)
    from gethsharding_tpu.resilience.errors import DeadlineExceeded
    from gethsharding_tpu.serving import ServingConfig, ServingSigBackend
    from gethsharding_tpu.sigbackend import PythonSigBackend

    old_env = {k: os.environ.get(k) for k in
               ("GETHSHARDING_PERFWATCH_DIR", "GETHSHARDING_PERFWATCH_DUMP_S",
                "GETHSHARDING_PERFWATCH_LEDGER")}
    os.environ["GETHSHARDING_PERFWATCH_DIR"] = os.path.join(tmp, "blackbox")
    os.environ["GETHSHARDING_PERFWATCH_DUMP_S"] = "0"
    os.environ["GETHSHARDING_PERFWATCH_LEDGER"] = ledger.path
    try:
        schedule = ChaosSchedule(
            seed=7, rules={"dispatch.ecrecover_addresses": 1})
        serving = ServingSigBackend(
            ChaosSigBackend(PythonSigBackend(), schedule, hang_s=2.0),
            ServingConfig(flush_us=200.0, watchdog_s=0.2))
        try:
            try:
                serving.ecrecover_addresses([b"\x11" * 32], [b"\x22" * 65])
                raise AssertionError("hung dispatch did not fail")
            except DeadlineExceeded:
                pass  # the watchdog fired — the trigger under test
            deadline = time.monotonic() + 10.0
            bundle = None
            while time.monotonic() < deadline:
                RECORDER.flush()
                base = os.environ["GETHSHARDING_PERFWATCH_DIR"]
                dirs = sorted(os.listdir(base)) if os.path.isdir(base) \
                    else []
                if dirs:
                    bundle = os.path.join(base, dirs[-1])
                    break
                time.sleep(0.05)
            assert bundle is not None, "watchdog fired but no bundle"
            required = ("manifest.json", "events.json", "spans.json",
                        "metrics.json", "wire.json", "ledger_tail.jsonl")
            present = sorted(os.listdir(bundle))
            missing = [f for f in required if f not in present]
            assert not missing, f"bundle incomplete: missing {missing}"
            events = json.load(open(os.path.join(bundle, "events.json")))
            kinds = {e["kind"] for e in events}
            assert "watchdog_timeout" in kinds, kinds
            assert "chaos_decision" in kinds, kinds
            snapshot = json.load(open(os.path.join(bundle,
                                                   "metrics.json")))
            assert "resilience/watchdog/timeouts" in snapshot
            tail = [json.loads(line) for line in
                    open(os.path.join(bundle, "ledger_tail.jsonl"))]
            assert tail, "ledger tail empty in the bundle"
            out["bundle"] = bundle
            out["bundle_files"] = present
            out["bundle_events"] = sorted(kinds)
            out["bundle_ledger_tail"] = len(tail)
        finally:
            serving.close()
    finally:
        for key, val in old_env.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val

    # -- part 4: the hot-path overhead budget ------------------------------
    serving = ServingSigBackend(PythonSigBackend(),
                                ServingConfig(flush_us=500.0))
    try:
        serving.ecrecover_addresses([], [])  # warm the threads
        n = 100
        t0 = time.perf_counter()
        for i in range(n):
            serving.ecrecover_addresses(
                [bytes([i % 251]) * 32], [b"\x00" * 65])
        per_request_s = (time.perf_counter() - t0) / n
    finally:
        serving.close()
    arr = _np.zeros(8, _np.int32)
    wire = {"wire_bytes": 1024, "g2_wire_bytes": 0, "pk_hit_bytes": 1024,
            "pk_rows": 100, "pk_hit_rows": 100, "resident": True,
            "wire": "i32"}
    m = 20_000
    t0 = time.perf_counter()
    for _ in range(m):
        dt = DeviceTimer("overhead_probe")
        dt.dispatched()
        dt.pull(arr)
        dt.done()
        RECORDER.record_wire("overhead_probe", wire)
    per_dispatch_s = (time.perf_counter() - t0) / m
    overhead_pct = 100.0 * per_dispatch_s / per_request_s
    assert overhead_pct < 2.0, (
        f"perfwatch timer+recorder overhead {overhead_pct:.3f}% of a "
        f"serving request ({per_dispatch_s * 1e6:.2f}us vs "
        f"{per_request_s * 1e6:.1f}us) breaches the 2% budget")
    out["overhead_pct"] = round(overhead_pct, 4)
    out["per_dispatch_us"] = round(per_dispatch_s * 1e6, 3)
    out["per_request_us"] = round(per_request_s * 1e6, 1)
    out["platform"] = "host"
    assert threading.active_count() < 100  # no thread leak from the loop
    # the suspect DRILL above (part 2) incremented the process-global
    # timer_suspect counter on purpose; resync the emitter's mark so
    # the headline record of this mode is not stamped invalid by its
    # own demonstration
    global _SUSPECT_MARK
    _SUSPECT_MARK = perfwatch.suspect_count()
    return out


# == devscope closed-loop acceptance (bench.py --devscope) =================


def measure_devscope() -> dict:
    """The device-introspection plane's acceptance run, closed-loop:

    1. **The storm detector fires exactly once.** An injected recompile
       storm (unbucketed traffic widening the compiled-shape set past
       the window threshold) must raise ONE `recompile_storm` recorder
       event and one `storms` tick — not one per fresh shape — while a
       steady-state stream of cache hits plus the occasional genuinely
       new bucket raises nothing.
    2. **A near-OOM leaves a census.** A simulated device at 95% HBM
       utilization must fire the flight recorder's dump path, and the
       resulting bundle's event ring must contain the `hbm_near_oom`
       event WITH the buffer census attributing live buffers to their
       registered owner.
    3. **It all stays cheap.** The sampling profiler's per-tick cost ×
       its rate plus the memory poller's per-poll cost ÷ its interval —
       the fraction of wall time the plane consumes while a serving
       request runs — is measured against a real serving request and
       asserted <2% (the same budget bar as tracing/SLO/perfwatch)."""
    import tempfile

    from gethsharding_tpu import devscope
    from gethsharding_tpu import metrics as _metrics
    from gethsharding_tpu.devscope import (CompileWatch, MemoryPoller,
                                           SamplingProfiler)
    from gethsharding_tpu.perfwatch.recorder import RECORDER

    out: dict = {}
    tmp = tempfile.mkdtemp(prefix="bench_devscope_")
    # the drills run against ISOLATED metric registries: an injected
    # storm or a fake 15-GiB device must exercise the detectors without
    # latching this process's real devscope/* rows (recorder events
    # stay global on purpose — they ARE the acceptance evidence)
    drill_reg = _metrics.Registry()

    # -- part 1: the recompile-storm detector, exactly once ---------------
    def _storm_events() -> int:
        return sum(1 for e in RECORDER.events()
                   if e["kind"] == "recompile_storm")

    watch = CompileWatch(storm_shapes=8, storm_window_s=30.0,
                         registry=drill_reg)
    events_before = _storm_events()
    for _ in range(64):  # steady state: the same bucketed shape, hits
        watch.saw("bls_committee", (128, 144), False)
    watch.saw("bls_committee", (160, 144), True)  # one honest new bucket
    assert watch.storms == 0, "a single fresh shape must not be a storm"
    assert _storm_events() == events_before
    for i in range(16):  # the storm: unbucketed widths flooding in
        watch.saw("bls_committee", (100 + i, 144), True)
    assert watch.storms == 1, (
        f"injected recompile storm raised {watch.storms} times, want 1")
    for i in range(16, 32):  # an ONGOING storm must not re-raise
        watch.saw("bls_committee", (100 + i, 144), True)
    assert watch.storms == 1, "ongoing storm re-raised the detector"
    storm_events = _storm_events() - events_before
    assert storm_events == 1, (
        f"{storm_events} recompile_storm recorder events, want exactly 1")
    assert watch.storm_active(), "storm gauge should still be latched"
    out["storm_raised"] = watch.storms
    out["storm_recorder_events"] = storm_events
    out["storm_fresh_shapes"] = 33

    # -- part 2: simulated near-OOM -> bundle with the buffer census ------
    class _Buf:
        def __init__(self, nbytes, shape):
            self.nbytes = nbytes
            self.shape = shape
            self.dtype = "int32"

    bufs = [_Buf(48 << 20, (1024, 135, 2, 25)),
            _Buf(16 << 20, (1024, 135, 2, 25)),
            _Buf(4 << 20, (128, 144))]

    class _HotDevice:
        id = 0
        platform = "tpu"

        def memory_stats(self):
            return {"bytes_in_use": int(15.2 * (1 << 30)),
                    "peak_bytes_in_use": int(15.4 * (1 << 30)),
                    "bytes_limit": 16 << 30}

    devscope.register_owner(
        "bench_demo_plane",
        claimed_fn=lambda: sum(b.nbytes for b in bufs),
        buffers_fn=lambda: list(bufs))
    old_env = {k: os.environ.get(k) for k in
               ("GETHSHARDING_PERFWATCH_DIR", "GETHSHARDING_PERFWATCH_DUMP_S")}
    os.environ["GETHSHARDING_PERFWATCH_DIR"] = os.path.join(tmp, "blackbox")
    os.environ["GETHSHARDING_PERFWATCH_DUMP_S"] = "0"
    try:
        poller = MemoryPoller(interval_s=60.0,
                              devices_fn=lambda: [_HotDevice()],
                              buffers_fn=lambda: list(bufs),
                              registry=drill_reg)
        readings = poller.poll_once()
        assert readings["d0"]["limit"] == 16 << 30
        deadline = time.monotonic() + 10.0
        bundle = None
        while time.monotonic() < deadline:
            RECORDER.flush()
            base = os.environ["GETHSHARDING_PERFWATCH_DIR"]
            dirs = sorted(os.listdir(base)) if os.path.isdir(base) else []
            if dirs:
                bundle = os.path.join(base, dirs[-1])
                break
            time.sleep(0.05)
        assert bundle is not None, "near-OOM fired but no bundle appeared"
        events = json.load(open(os.path.join(bundle, "events.json")))
        oom = [e for e in events if e["kind"] == "hbm_near_oom"]
        assert oom, f"no hbm_near_oom event in the bundle: " \
                    f"{sorted({e['kind'] for e in events})}"
        census = oom[-1]["detail"]["census"]
        assert census["live_buffers"] == len(bufs), census
        owner_slot = census["by_owner"].get("bench_demo_plane")
        assert owner_slot and owner_slot["bytes"] == sum(
            b.nbytes for b in bufs), census["by_owner"]
        assert not census["owners"]["bench_demo_plane"]["drifted"]
        # a second poll at the same utilization must NOT re-dump: the
        # episode latch holds until utilization clears the hysteresis
        near_oom_before = poller.describe()["near_oom_events"]
        poller.poll_once()
        assert poller.describe()["near_oom_events"] == near_oom_before, (
            "near-OOM re-fired inside one episode")
        out["bundle"] = bundle
        out["census_buffers"] = census["live_buffers"]
        out["census_owned_bytes"] = owner_slot["bytes"]
    finally:
        devscope.unregister_owner("bench_demo_plane")
        for key, val in old_env.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val

    # -- part 3: sampler + poller overhead vs a serving request -----------
    from gethsharding_tpu.serving import ServingConfig, ServingSigBackend
    from gethsharding_tpu.sigbackend import PythonSigBackend

    serving = ServingSigBackend(PythonSigBackend(),
                                ServingConfig(flush_us=500.0))
    try:
        serving.ecrecover_addresses([], [])  # warm the threads
        n = 100
        t0 = time.perf_counter()
        for i in range(n):
            serving.ecrecover_addresses(
                [bytes([i % 251]) * 32], [b"\x00" * 65])
        per_request_s = (time.perf_counter() - t0) / n

        # the sampler's per-tick cost, measured with the serving
        # threads live (a tick walks EVERY thread's stack — an idle
        # process would understate it)
        # default hz — the rate we charge; isolated registry (a probe
        # loop must not inflate the process sample counter)
        sampler = SamplingProfiler(registry=drill_reg)
        m = 500
        t0 = time.perf_counter()
        for _ in range(m):
            sampler.sample_once()
        tick_s = (time.perf_counter() - t0) / m
        assert sampler.collapsed(), "sampler collected no stacks"
    finally:
        serving.close()
    class _CoolDevice:
        # the overhead probe's device sits WELL below the near-OOM
        # threshold: part 2 already restored the perfwatch env, so a
        # 95% device here would dump real bundles into cwd and bill
        # the background dump thread to the poll-cost timing
        id = 0
        platform = "tpu"

        def memory_stats(self):
            return {"bytes_in_use": 8 << 30,
                    "peak_bytes_in_use": 9 << 30,
                    "bytes_limit": 16 << 30}

    idle_poller = MemoryPoller(interval_s=None,
                               devices_fn=lambda: [_CoolDevice()],
                               buffers_fn=lambda: [],
                               registry=drill_reg)
    m = 200
    t0 = time.perf_counter()
    for _ in range(m):
        idle_poller.poll_once()
    poll_s = (time.perf_counter() - t0) / m
    # the plane's duty cycle: fraction of any wall interval (and hence
    # of any serving request running through it) spent in devscope
    duty = sampler.hz * tick_s + poll_s / idle_poller.interval_s
    overhead_pct = 100.0 * duty
    assert overhead_pct < 2.0, (
        f"devscope sampler+poller overhead {overhead_pct:.3f}% of a "
        f"serving request (tick {tick_s * 1e6:.1f}us x {sampler.hz}Hz + "
        f"poll {poll_s * 1e6:.1f}us / {idle_poller.interval_s}s) "
        f"breaches the 2% budget")
    out["overhead_pct"] = round(overhead_pct, 4)
    out["sampler_tick_us"] = round(tick_s * 1e6, 2)
    out["sampler_hz"] = sampler.hz
    out["poll_us"] = round(poll_s * 1e6, 2)
    out["poll_interval_s"] = idle_poller.interval_s
    out["per_request_us"] = round(per_request_s * 1e6, 1)
    out["platform"] = "host"
    return out


# == fleettrace closed-loop acceptance (bench.py --fleettrace) =============


def _read_boot_line(proc, timeout_s: float = 60.0) -> dict:
    """Read the one-line {"host","port"} JSON a chain_server / fleet
    frontend prints once listening (bounded: a child that dies or never
    binds fails the bench instead of hanging it)."""
    import select

    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        ready, _, _ = select.select([proc.stdout], [], [], 0.25)
        if not ready:
            assert proc.poll() is None, (
                f"child exited rc {proc.returncode} before binding")
            continue
        line = proc.stdout.readline()
        assert line, f"child closed stdout (rc {proc.poll()})"
        line = line.strip()
        if line.startswith(b"{"):
            return json.loads(line)
    raise AssertionError("child never printed its boot line")


def measure_fleettrace() -> dict:
    """The fleettrace closed-loop acceptance run, three processes end
    to end:

    1. **One request, one tree, three processes.** A fleet frontend
       (``--fleettrace``, owning the collector) balances 2 chain_server
       replicas (``--fleettrace-export`` back to the frontend); this
       bench process exports its own client spans the same way. One
       interactive ``shard_verifyAggregates`` must assemble into ONE
       trace whose spans carry >= 3 distinct pids, and the critical-
       path segments must sum to the INDEPENDENTLY measured end-to-end
       wall time within 10% (the self-time telescoping identity,
       checked against a clock the collector never saw).
    2. **A breach leaves a cross-process exemplar.** With the
       interactive latency target forced impossibly low, a burst of
       routed requests breaches the SLO in the frontend; the breach
       onset dumps a flight-recorder bundle whose ``exemplars.json``
       must contain an assembled >= 3-process trace.
    3. **Collection stays cheap.** Per-span record + encode + ingest
       cost (measured on isolated instruments) x the measured spans-
       per-request, as a fraction of the measured request, asserted
       under the 2% observability budget."""
    import socket
    import tempfile

    from gethsharding_tpu import fleettrace, metrics as _metrics, tracing
    from gethsharding_tpu.crypto import bn256 as bls
    from gethsharding_tpu.crypto import secp256k1 as ecdsa
    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.rpc import codec
    from gethsharding_tpu.rpc.client import RPCClient

    out: dict = {}
    tmp = tempfile.mkdtemp(prefix="bench_fleettrace_")
    bundles = os.path.join(tmp, "blackbox")
    # reserve the frontend port up front: replicas need their export
    # endpoint BEFORE the frontend can exist (it dials them to boot),
    # and a failed export batch is absorbed + retried by design
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    fe_port = sock.getsockname()[1]
    sock.close()

    child_env = {**os.environ, "JAX_PLATFORMS": "cpu",
                 "GETHSHARDING_FLEETTRACE_INTERVAL_MS": "50"}
    fe_env = {**child_env,
              "GETHSHARDING_FLEETTRACE_SAMPLE": "1.0",
              "GETHSHARDING_FLEETTRACE_LINGER_S": "0.4",
              "GETHSHARDING_PERFWATCH_DIR": bundles,
              "GETHSHARDING_PERFWATCH_DUMP_S": "0",
              # impossible interactive latency target: every routed
              # request is budget-bad, so phase 2's burst breaches
              "GETHSHARDING_SLO_INTERACTIVE_P99_MS": "0.001"}
    old_env = {k: os.environ.get(k)
               for k in ("GETHSHARDING_FLEETTRACE_INTERVAL_MS",)}
    os.environ["GETHSHARDING_FLEETTRACE_INTERVAL_MS"] = "50"

    children = []
    client = None
    try:
        replicas = []
        for i in range(2):
            proc = subprocess.Popen(
                [sys.executable, "-m", "gethsharding_tpu.rpc.chain_server",
                 "--port", "0", "--sigbackend", "python",
                 "--fleettrace-export", f"127.0.0.1:{fe_port}"],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                cwd=REPO, env=child_env)
            children.append(proc)
            replicas.append(_read_boot_line(proc))
        frontend = subprocess.Popen(
            [sys.executable, "-m", "gethsharding_tpu.fleet.frontend",
             "--port", str(fe_port), "--fleettrace",
             *sum((["--replica", f"{r['host']}:{r['port']}"]
                   for r in replicas), [])],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            cwd=REPO, env=fe_env)
        children.append(frontend)
        boot = _read_boot_line(frontend)
        assert boot["port"] == fe_port, boot

        # this process exports its own client spans to the collector:
        # the third process in every assembled tree
        fleettrace.boot_exporter(f"127.0.0.1:{fe_port}", label="bench")
        client = RPCClient("127.0.0.1", fe_port, timeout=60.0)

        # -- part 1: one interactive request -> one 3-process tree --------
        header = b"fleettrace-bench"
        keys = [bls.bls_keygen(bytes([i + 1])) for i in range(3)]
        agg_sig = bls.bls_aggregate_sigs(
            [bls.bls_sign(header, sk) for sk, _ in keys])
        agg_pk = bls.bls_aggregate_pks([pk for _, pk in keys])
        call_args = ([codec.enc_bytes(header)], [codec.enc_g1(agg_sig)],
                     [codec.enc_g2(agg_pk)], "interactive")
        for _ in range(2):  # warm replica dial + serving threads
            assert client.call("shard_verifyAggregates",
                               *call_args) == [True]
        with tracing.span("bench/fleettrace_request") as probe:
            t0 = time.perf_counter()
            got = client.call("shard_verifyAggregates", *call_args)
            wall_s = time.perf_counter() - t0
        assert got == [True], got
        trace_id = probe.trace_id
        fleettrace.EXPORTER.flush()

        exemplar = None
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline and exemplar is None:
            for ex in client.call("shard_traceExemplars", 32):
                if ex["trace_id"] == trace_id:
                    exemplar = ex
                    break
            if exemplar is None:
                time.sleep(0.2)
        assert exemplar is not None, (
            "the measured request never assembled into a retained trace")
        pids = {span.get("pid") for span in exemplar["spans"]}
        pids.discard(None)
        assert len(pids) >= 3, (
            f"assembled trace spans {len(pids)} processes, want >= 3 "
            f"(bench + frontend + replica): {sorted(pids)}")
        attr = exemplar["attribution"]
        seg_sum_s = sum(attr["segments"].values())
        identity = abs(seg_sum_s - wall_s) / wall_s
        assert identity <= 0.10, (
            f"critical-path segments sum {seg_sum_s * 1e3:.2f} ms vs "
            f"measured wall {wall_s * 1e3:.2f} ms "
            f"({identity * 100:.1f}% apart, bar 10%) — "
            f"segments {attr['segments']}")
        tables = client.call("shard_traceAttribution")
        assert tables["classes"].get("interactive"), tables["classes"]
        assert tables["traces"]["assembled"] >= 1, tables
        out["processes"] = len(pids)
        out["spans_per_request"] = len(exemplar["spans"])
        out["wall_ms"] = round(wall_s * 1e3, 2)
        out["segment_sum_ms"] = round(seg_sum_s * 1e3, 2)
        out["identity_gap_pct"] = round(identity * 100, 2)
        out["segments_ms"] = {k: round(v * 1e3, 3)
                              for k, v in attr["segments"].items()
                              if v > 0}

        # -- part 2: SLO breach -> bundle with cross-process exemplar -----
        digests, sigs = [], []
        for i in range(4):
            priv = int.from_bytes(keccak256(b"ft-%d" % i), "big") % ecdsa.N
            digest = keccak256(b"ft-msg-%d" % i)
            digests.append(codec.enc_bytes(digest))
            sigs.append(codec.enc_bytes(
                ecdsa.sign(digest, priv).to_bytes65()))
        for _ in range(12):  # >= min_events inside one refresh window
            client.call("shard_ecrecover", digests, sigs, "interactive")
        time.sleep(1.1)  # the burn-gauge refresh is throttled to ~1/s
        for _ in range(3):
            client.call("shard_ecrecover", digests, sigs, "interactive")
        bundle = None
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline and bundle is None:
            if os.path.isdir(bundles):
                for name in sorted(os.listdir(bundles)):
                    path = os.path.join(bundles, name)
                    if "slo_breach" in name and os.path.exists(
                            os.path.join(path, "exemplars.json")):
                        bundle = path
                        break
            if bundle is None:
                time.sleep(0.2)
        assert bundle is not None, (
            "the injected SLO breach never dumped a flight-recorder "
            "bundle with exemplars.json")
        exemplars = json.load(open(os.path.join(bundle, "exemplars.json")))
        cross = [ex for ex in exemplars
                 if len({s.get("pid") for s in ex["spans"]}
                        - {None}) >= 3]
        assert cross, (
            f"no cross-process exemplar in the breach bundle "
            f"({len(exemplars)} exemplars)")
        events = json.load(open(os.path.join(bundle, "events.json")))
        assert any(e["kind"] == "slo_breach" for e in events), (
            sorted({e["kind"] for e in events}))
        out["breach_bundle"] = bundle
        out["bundle_exemplars"] = len(exemplars)
        out["bundle_cross_process"] = len(cross)
    finally:
        if client is not None:
            client.close()
        fleettrace.shutdown()
        for proc in children:
            proc.terminate()
        for proc in children:
            try:
                proc.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                proc.kill()
        for key, val in old_env.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val

    # -- part 3: collection overhead vs the measured request ---------------
    # per-span costs on ISOLATED instruments (the probe loops must not
    # pollute the process tracer/collector), charged at the strictest
    # model — every span of the measured request pays record + encode +
    # ingest — against the request it observed
    tracer = tracing.Tracer(registry=_metrics.Registry())
    tracer.enabled = True
    tracer.enable_export(8192)
    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        tracer.record("serving/bench/queue_wait", 0.0, 0.001,
                      trace_id=i, tags={"klass": "interactive"})
    record_s = (time.perf_counter() - t0) / n
    batch, _ = tracer.drain_export(512)
    t0 = time.perf_counter()
    for _ in range(16):
        rows = codec.enc_spans(batch)
    enc_s = (time.perf_counter() - t0) / (16 * len(batch))
    sink = fleettrace.TraceCollector(_metrics.Registry(),
                                     max_traces=65536, linger_s=3600.0,
                                     sample=0.0)
    payload = {"pid": os.getpid(), "label": "bench", "clock_offset_us": 0.0,
               "dropped": 0, "spans": rows}
    m = 16
    t0 = time.perf_counter()
    for _ in range(m):
        sink.ingest_payload(dict(payload))
    ingest_s = (time.perf_counter() - t0) / (m * len(batch))
    per_span_s = record_s + enc_s + ingest_s
    overhead_pct = (100.0 * out["spans_per_request"] * per_span_s
                    / wall_s)
    assert overhead_pct < 2.0, (
        f"fleettrace collection overhead {overhead_pct:.3f}% of the "
        f"measured request ({out['spans_per_request']} spans x "
        f"{per_span_s * 1e6:.2f}us vs {wall_s * 1e3:.2f} ms) breaches "
        f"the 2% budget")
    out["overhead_pct"] = round(overhead_pct, 4)
    out["record_us"] = round(record_s * 1e6, 3)
    out["encode_us"] = round(enc_s * 1e6, 3)
    out["ingest_us"] = round(ingest_s * 1e6, 3)
    out["platform"] = "host"
    return out


# == autotune orchestration ================================================


def _heavy_config(cfg: dict) -> bool:
    """Configs whose FIRST compile can legitimately exceed the normal
    per-probe timeout (mega-kernel Mosaic compiles, static unrolls).
    They get a longer probe window."""
    return (cfg.get("GETHSHARDING_TPU_PAIR_UNROLL", "0") != "0"
            or "mega" in (cfg.get("GETHSHARDING_TPU_FINALEXP", ""),
                          cfg.get("GETHSHARDING_TPU_MILLER", ""),
                          cfg.get("GETHSHARDING_TPU_AGG", "")))


class ConfigFailed(RuntimeError):
    """A sweep child died, timed out, failed its correctness gate or
    printed no result."""


def _run_config(cfg: dict, extras: bool = False) -> dict:
    """Measure one knob configuration in a child process and return its
    stats. A child that crashes, times out, fails the correctness gate
    or prints no stats line raises `ConfigFailed` with the reason — a
    dead child is a failure of the run, never a config quietly left
    out of the ranking."""
    # the probe must measure cfg and ONLY cfg: ambient exported
    # GETHSHARDING_TPU_* knobs would leak into every subprocess and trip
    # the mutually-exclusive knob validations (ValueError at import)
    env = {key: val for key, val in os.environ.items()
           if not key.startswith("GETHSHARDING_TPU_")}
    env.update(cfg)
    # the winner's extras pass (configs 1/2/4/5) compiles several extra
    # kernels, so it gets a budget of its own, scaled with the run's
    # overall budget knob so a capped run stays capped; heavy configs
    # get a longer window for their first Mosaic compile
    if extras:
        timeout = min(4200, max(560, 1.25 * SWEEP_BUDGET_S))
        if _kperiod_cache_ready(8):
            # the extras pass will also attempt the K-period sweep (a
            # fresh 8-period chain + two cold batch shapes)
            timeout = max(timeout, min(6000, 4 * SWEEP_BUDGET_S))
        env["GETHSHARDING_BENCH_EXTRAS"] = "1"
    else:
        timeout = min(1800 if _heavy_config(cfg) else 560, SWEEP_BUDGET_S)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--single"],
            env=env, capture_output=True, text=True, timeout=timeout,
            cwd=REPO)
    except subprocess.TimeoutExpired as exc:
        raise ConfigFailed(
            f"config {cfg} timed out after {timeout:.0f} s") from exc
    if proc.returncode != 0:
        raise ConfigFailed(
            f"config {cfg} child exited {proc.returncode}:\n"
            f"{proc.stderr[-2000:]}")
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            stats = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(stats, dict) and "sig_rate" in stats:
            return stats
    raise ConfigFailed(f"config {cfg} child printed no stats line")


def _sweep_fingerprint() -> str:
    """Identity of the config set: a cache written for a different sweep
    (older knob set) must not short-circuit the new sweep."""
    import hashlib

    return hashlib.sha256(
        json.dumps(CONFIGS, sort_keys=True).encode()).hexdigest()[:12]


def _cache_path() -> str:
    return os.path.join(REPO, ".bench_autotune.json")


def ensure_workload_cache() -> None:
    """Build the signing workload ONCE in the orchestrating process (host
    scalar crypto only, no accelerator) so each sweep subprocess loads it
    from disk instead of paying ~3 minutes."""
    k = int(os.environ.get("GETHSHARDING_BENCH_KPERIOD_MAX", "1"))
    manager, accounts, _roots, digests, _periods = _bench_identities(k)
    _load_or_build_vote_sigs(accounts, manager, digests)


_SUSPECT_MARK: "int | None" = None


def _emit(metric: str, value, unit: str, vs_baseline, extra: dict,
          workload: "str | None" = None, source: str = "bench") -> None:
    """THE one result emitter: prints the driver's JSON line AND appends
    the same measurement to the perfwatch benchmark ledger (one schema,
    one writer — per-mode extras dicts can no longer drift). A record
    taken while the device-timer self-check fired (`block_until_ready`
    no-oped under the measurement — the r4 hazard) is stamped invalid so
    the regression gate never baselines a lying timing."""
    global _SUSPECT_MARK
    print(json.dumps({"metric": metric, "value": value, "unit": unit,
                      "vs_baseline": vs_baseline, "extra": extra}))
    try:
        from gethsharding_tpu.perfwatch import record_bench, suspect_count

        suspects_now = suspect_count()
        suspects = suspects_now - (_SUSPECT_MARK or 0)
        _SUSPECT_MARK = suspects_now
        record_bench(metric=metric, value=value, unit=unit,
                     vs_baseline=vs_baseline, extra=extra,
                     workload=workload or metric, source=source,
                     suspects=suspects)
    except Exception as exc:  # noqa: BLE001 - the ledger is additive:
        # a read-only checkout must still print the driver line
        print(f"# perfwatch ledger write failed: {exc!r}", file=sys.stderr)


def _print_metric(sig_rate: float, stats: dict, knobs: str) -> None:
    """The headline metric line (single output contract for the
    autotuned and fallback paths), routed through `_emit`."""
    extra = {key: val for key, val in stats.items() if key != "sig_rate"}
    try:
        # code provenance: the tree this number measured
        extra["git"] = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=REPO,
            capture_output=True, text=True, timeout=10).stdout.strip()
    except (subprocess.SubprocessError, OSError):
        pass
    _emit("notary_sig_verifications_per_sec", sig_rate,
          (f"sigs/sec (100-shard period audit, on-device 135-vote "
           f"BLS aggregation+verification, protocol-generated "
           f"workload, opt-ate bn256, {knobs})"),
          round(sig_rate / 100_000.0, 4), extra)


def _require_accelerator() -> None:
    """Fail now, with the reason, when no accelerator answers — before
    minutes of workload signing, and from a child so THIS process stays
    off JAX (a parent that touched JAX would hold the chip its
    measuring children need). Not a fallback switch: the only way to
    measure on the CPU is to say so with GETHSHARDING_BENCH_CPU=1
    (JAX_PLATFORMS=cpu alone does not make the CPU a benchmark
    target)."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "from gethsharding_tpu.ops.device import device_record; "
         "print(device_record()['platform'])"],
        capture_output=True, text=True, cwd=REPO)
    platform = (proc.stdout.strip().splitlines() or [""])[-1]
    if proc.returncode != 0 or platform in ("", "cpu"):
        tail = proc.stderr.strip().splitlines()[-1:] or [
            f"JAX resolved to platform {platform!r}"]
        sys.exit(f"bench: no accelerator, nothing measured ({tail[0]})")


def main() -> None:
    # the device-introspection stamp: every ledger record this process
    # emits carries the peak-HBM watermark + cumulative compile cost
    # (devscope.ledger_fields, polled on demand at each append — no
    # background thread perturbing the measurements)
    try:
        from gethsharding_tpu import devscope as _devscope

        _devscope.boot(start_poller=False)
    except Exception:  # noqa: BLE001 - the stamp is additive
        pass

    if "--single" in sys.argv:
        print(json.dumps(measure_single()))
        return

    if "--trace" in sys.argv:
        # profile ONE serving benchmark run with the span tracer on:
        # every coalesced request's queue_wait / batch_assembly /
        # device_dispatch attribution lands in a Chrome trace-event JSON
        # (open in Perfetto) — the artifact that says WHERE a slow
        # request spent its time, which the aggregate timers cannot
        from gethsharding_tpu import tracing

        out_path = os.environ.get(
            "GETHSHARDING_TRACE_OUT", os.path.join(REPO, "bench_trace.json"))
        if "--trace-out" in sys.argv:
            idx = sys.argv.index("--trace-out")
            if idx + 1 < len(sys.argv):
                out_path = sys.argv[idx + 1]
        tracing.enable(ring_spans=65536)
        stats = measure_serving()
        events = tracing.write_chrome_trace(out_path)
        requests = sum(
            1 for rec in tracing.TRACER.recent_spans()
            if rec["name"].endswith("/request"))
        _emit("serving_trace_profile", stats["serving_rate"],
              (f"verifs/sec ({stats['clients']} concurrent clients, "
               f"span-traced serving run, {stats['backend']} "
               f"backend)"),
              round(stats["serving_rate"]
                    / max(stats["direct_rate"], 1e-9), 4),
              {**{k: v for k, v in stats.items() if k != "serving_rate"},
               "trace_out": out_path,
               "trace_events": events,
               "traced_requests": requests})
        return

    if "--resident" in sys.argv:
        # cold-vs-warm transfer attribution for the device-resident pk
        # planes: the warm G2 byte count is THE acceptance number (zero
        # when residency is on), the cold/warm delta is the per-dispatch
        # transfer the cache removes
        stats = measure_resident()
        _emit("audit_warm_wire_bytes_per_dispatch",
              stats["wire_bytes_warm"],
              (f"bytes over the host->device link per warm "
               f"100-shard audit dispatch (cold "
               f"{stats['wire_bytes_cold']} B; resident="
               f"{stats['resident']}, {stats['platform']})"),
              round(stats["wire_bytes_warm"]
                    / max(1, stats["wire_bytes_cold"]), 4),
              {k: v for k, v in stats.items() if k != "wire_bytes_warm"})
        return

    if "--overlap" in sys.argv:
        # sequential vs overlapped audit pipeline (marshal N+1 while N
        # executes); >= 1.0 means the overlap pays for itself
        stats = measure_overlap()
        _emit("audit_overlap_ratio", stats["overlap_ratio"],
              (f"sequential/overlapped wall ratio over "
               f"{stats['k_periods']} periods "
               f"({stats['platform']})"),
              stats["overlap_ratio"],
              {k: v for k, v in stats.items() if k != "overlap_ratio"})
        return

    if "--mesh" in sys.argv:
        # the multi-chip audit closed loop: tri-path bit-identity
        # (scalar / single-device / D-device mesh), exactly one
        # cross-device collective per compiled step, disjoint
        # per-device cache-shard ownership in the devscope census —
        # recorded as the `multichip_audit` workload group so the
        # noise-aware gate tracks the mesh rate like any other
        stats = measure_mesh()
        _emit("multichip_audit_sig_rate", stats["sig_rate"],
              (f"sigs/sec ({stats['rows']}-committee seeded audit on "
               f"{stats['n_devices']} virtual {stats['platform']} devices, "
               f"one pjit step, {stats['collectives_per_step']} "
               f"collective/step, verdicts bit-identical to scalar + "
               f"single-device)"),
              round(stats["sig_rate"] / 100_000.0, 6),
              {k: v for k, v in stats.items() if k != "sig_rate"},
              workload="multichip_audit")
        return

    if "--precomp" in sys.argv:
        # the fixed-base precomputation closed loop: tri-path verdict
        # bit-identity (scalar / precomp / recompute, hostile rows
        # included), warm zero-G2 wire, and the HLO op census proving
        # the fixed-argument point arithmetic is absent — recorded as
        # the `precomp_audit` workload so the noise-aware gate tracks
        # the precomp rate like any other
        stats = measure_precomp()
        _emit("precomp_audit_sig_rate", stats["sig_rate"],
              (f"sigs/sec ({stats['rows']}-committee seeded audit, warm "
               f"fixed-base line tables, zero G2 wire bytes, "
               f"{stats['hlo_multiplies_precomp']} HLO multiplies vs "
               f"{stats['hlo_multiplies_recompute']} recompute, verdicts "
               f"bit-identical to scalar + recompute, "
               f"{stats['platform']})"),
              round(stats["sig_rate"] / 100_000.0, 6),
              {k: v for k, v in stats.items() if k != "sig_rate"},
              workload="precomp_audit")
        if stats.get("config5_stress_shards_per_s"):
            _emit("precomp_config5_stress_shards_per_s",
                  stats["config5_stress_shards_per_s"],
                  (f"shards/sec fused stress step "
                   f"({stats['config5_shards']} shards, committee "
                   f"{stats['config5_committee']}, precomp-era tree, "
                   f"{stats['platform']})"),
                  None,
                  {k: v for k, v in stats.items()
                   if k != "config5_stress_shards_per_s"},
                  workload="precomp_stress")
        return

    if "--composed" in sys.argv:
        # resident + overlap (+ precomp) composed: the K-period
        # overlapped pipeline over warm line tables — the composed
        # record the 05_* probes have queued since PR 3
        stats = measure_composed()
        _emit("composed_audit_sig_rate", stats["sig_rate"],
              (f"sigs/sec ({stats['k_periods']}-period overlapped "
               f"audit, resident={stats['resident']}, "
               f"precomp={stats['precomp']}, {stats['platform']})"),
              round(stats["sig_rate"] / 100_000.0, 6),
              {k: v for k, v in stats.items() if k != "sig_rate"},
              workload="composed_audit")
        return

    if "--chaos" in sys.argv:
        # failover availability under a seeded chaos schedule: the
        # value is the fraction of calls answered correctly while the
        # primary faults; extras carry the breaker's full open ->
        # half-open-probe -> closed cycle counters
        stats = measure_chaos()
        injected_desc = (
            f"{stats['corruptions_injected']} silent corruptions "
            f"({stats['corruptions_detected']} detected)"
            if stats["mode"] == "corrupt"
            else f"{stats['injected_faults']} injected faults")
        _emit("chaos_availability", stats["chaos_availability"],
              (f"fraction of {stats['calls']} calls answered "
               f"correctly under seeded chaos (rate "
               f"{stats['rate']}, {injected_desc}, "
               f"{stats['primary']} primary, "
               f"{stats['platform']})"),
              stats["chaos_availability"],
              {k: v for k, v in stats.items()
               if k != "chaos_availability"})
        return

    if "--soundness" in sys.argv:
        # the continuous integrity audit's two acceptance numbers:
        # audit overhead per dispatch (asserted <2% at the default
        # sample rate) and closed-loop silent-corruption detection
        # within the dispatch budget detection_probability predicts
        stats = measure_soundness()
        _emit("soundness_overhead_pct", stats["overhead_pct"],
              (f"% of a {stats['rows']}-row ecrecover dispatch "
               f"spent on the soundness audit at rate "
               f"{stats['default_rate']} (corruption tripped the "
               f"breaker in {stats['dispatches_to_trip']} of the "
               f"predicted {stats['predicted_budget_p999']} "
               f"dispatches, {stats['platform']})"),
              round(stats["overhead_pct"] / 2.0, 4),
              {k: v for k, v in stats.items() if k != "overhead_pct"})
        return

    if "--das" in sys.argv:
        # data-availability sampling: full-fetch vs sampled bytes per
        # collation (the bandwidth->compute trade), with the batched
        # sample-verify throughput riding in the extras. The run IS the
        # acceptance check: zero body fetches, bytes within the
        # k-sample budget, batched verdicts == scalar.
        stats = measure_das()
        _emit("das_sampled_bytes_per_collation",
              stats["sampled_bytes_per_collation"],
              (f"bytes fetched per {stats['body_bytes']}-byte "
               f"collation at k={stats['k_samples']} sampled "
               f"chunks (full fetch: "
               f"{stats['full_fetch_bytes_per_collation']} B; "
               f"{stats['platform']})"),
              stats["bytes_ratio"],
              {key: val for key, val in stats.items()
               if key != "sampled_bytes_per_collation"})
        return

    if "--das-poly" in sys.argv:
        # polynomial-multiproof DAS: the proof-byte cut vs merkle
        # paths (the run asserts the ≥5× acceptance floor and the
        # constant-in-k proof size), with batched-vs-scalar multiproof
        # verify throughput riding in the extras, bit-identical.
        stats = measure_das_poly()
        _emit("das_poly_proof_bytes_per_collation",
              stats["poly_proof_bytes_per_collation"],
              (f"proof bytes per collation at "
               f"k={stats['k_samples']} sampled chunks (merkle: "
               f"{stats['merkle_proof_bytes_per_collation']} B — a "
               f"{stats['proof_bytes_cut']}x cut; batched verify "
               f"{stats['verify_rows_per_sec']} rows/s vs scalar "
               f"{stats['scalar_rows_per_sec']}, "
               f"{stats['platform']})"),
              round(stats["poly_proof_bytes_per_collation"]
                    / stats["merkle_proof_bytes_per_collation"], 4),
              {key: val for key, val in stats.items()
               if key != "poly_proof_bytes_per_collation"})
        return

    if "--perfwatch" in sys.argv:
        # the measurement substrate's own acceptance gate: the
        # regression check trips on an injected 1.3x slowdown (and only
        # then), a simulated no-op block_until_ready is caught by the
        # timer self-check and invalidates its record, a chaos-injected
        # dispatch hang produces a COMPLETE flight-recorder bundle, and
        # the whole layer stays under the 2% hot-path budget
        stats = measure_perfwatch()
        _emit("perfwatch_overhead_pct", stats["overhead_pct"],
              (f"% of a serving request spent on the perfwatch device "
               f"timer + flight-recorder ring "
               f"({stats['per_dispatch_us']}us vs "
               f"{stats['per_request_us']}us; gate tripped on "
               f"{','.join(stats['gate_tripped_on'])}, bundle "
               f"{len(stats['bundle_files'])} files, host)"),
              round(stats["overhead_pct"] / 2.0, 4),
              {k: v for k, v in stats.items() if k != "overhead_pct"})
        return

    if "--devscope" in sys.argv:
        # the device-introspection plane's acceptance gate: the
        # recompile-storm detector raises exactly once on an injected
        # storm (silent on steady state), a simulated near-OOM leaves a
        # flight-recorder bundle containing the attributed buffer
        # census, and the sampler+poller duty cycle stays under the 2%
        # serving-request budget
        stats = measure_devscope()
        _emit("devscope_overhead_pct", stats["overhead_pct"],
              (f"% of a serving request spent on the devscope sampler "
               f"({stats['sampler_tick_us']}us/tick x "
               f"{stats['sampler_hz']}Hz) + memory poller "
               f"({stats['poll_us']}us / {stats['poll_interval_s']}s); "
               f"storm raised {stats['storm_raised']}x, census "
               f"{stats['census_buffers']} buffers, host)"),
              round(stats["overhead_pct"] / 2.0, 4),
              {k: v for k, v in stats.items() if k != "overhead_pct"})
        return

    if "--fleettrace" in sys.argv:
        # the cross-process tracing closed loop: one interactive
        # request through bench -> frontend -> replica assembles into
        # one >= 3-process trace whose critical-path segments sum to
        # the independently measured wall time, an injected SLO breach
        # dumps a bundle carrying a cross-process exemplar, and the
        # collection plane stays under the 2% observability budget
        stats = measure_fleettrace()
        _emit("fleettrace_overhead_pct", stats["overhead_pct"],
              (f"% of the measured fleet request spent on span "
               f"collection ({stats['spans_per_request']} spans x "
               f"record {stats['record_us']}us + encode "
               f"{stats['encode_us']}us + ingest {stats['ingest_us']}us "
               f"vs {stats['wall_ms']} ms; {stats['processes']}-process "
               f"trace, segment-sum gap {stats['identity_gap_pct']}%, "
               f"host)"),
              round(stats["overhead_pct"] / 2.0, 4),
              {k: v for k, v in stats.items() if k != "overhead_pct"})
        return

    if "--serving" in sys.argv:
        # the serving-tier extra: coalesced verifications/sec for M
        # concurrent small-request clients, with the direct-backend
        # baseline riding in the same JSON line
        stats = measure_serving()
        _emit("serving_coalesced_verifications_per_sec",
              stats["serving_rate"],
              (f"verifs/sec ({stats['clients']} concurrent clients x "
               f"single-item ecrecover through the serving tier, "
               f"{stats['backend']} backend)"),
              round(stats["serving_rate"]
                    / max(stats["direct_rate"], 1e-9), 4),
              {k: v for k, v in stats.items() if k != "serving_rate"})
        return

    if "--elastic" in sys.argv:
        # the elastic-fleet acceptance gate: the cross-process
        # closed-loop soak — diurnal swing, autoscaler out AND in,
        # frontend killed -9 with actor failover, zero incorrect
        # verdicts (asserted inside; the soak also appends its own
        # fleet_elastic workload record through record_bench)
        stats = measure_elastic()
        _emit("fleet_elastic_soak_p99_ms", stats["p99_ms"],
              (f"interactive p99 ms across a 10x diurnal swing over "
               f"{stats['replicas']} replicas + 2 peered frontends "
               f"(autoscaler out x{stats['scale_out']} / "
               f"in x{stats['scale_in']}, one frontend killed -9, "
               f"{stats['failovers']} pool failovers, "
               f"{stats['clients']} clients, {stats['platform']})"),
              round(stats["p99_ms"] / max(stats["slo_ms"], 1e-9), 4),
              {k: v for k, v in stats.items()
               if k not in ("summary", "p99_ms", "endpoints")},
              workload="fleet_elastic")
        return

    if "--fleet" in sys.argv:
        # the fleet-serving acceptance gate: the traffic-model soak
        # (scripts/serving_stress.py --replicas) under a seeded chaos
        # schedule that trips one replica's breaker mid-soak. The run
        # IS the check: zero lost/mis-answered requests, the router
        # drains and re-enters the tripped replica through half-open
        # re-promotion, catchup_replay sheds first while interactive
        # sees zero sheds and holds its p99 SLO.
        stats = measure_fleet()
        _emit("fleet_interactive_p99_ms", stats["p99_ms"]["interactive"],
              (f"interactive p99 ms over a {stats['replicas']}"
               f"-replica routed fleet (SLO "
               f"{stats['slo_ms']['interactive']} ms; mid-soak "
               f"breaker trip + drain + re-entry; "
               f"{stats['clients']} mixed-class clients, "
               f"{stats['platform']})"),
              round(stats["p99_ms"]["interactive"]
                    / max(stats["slo_ms"]["interactive"], 1e-9), 4),
              {k: v for k, v in stats.items() if k != "p99_ms"}
              | {"p99_ms": stats["p99_ms"]})
        # the hedging closed loop: one replica transport-delayed 10x,
        # interactive p99 must improve >= 2x at <= 15% wasted
        # dispatches (asserted inside)
        hedge = measure_hedge()
        _emit("fleet_hedge_p99_improvement", hedge["improvement"],
              (f"x interactive p99 cut by hedging "
               f"({hedge['p99_ms_no_hedge']} ms -> "
               f"{hedge['p99_ms_hedged']} ms; one replica delayed "
               f"{hedge['delay_s'] * 1e3:.0f} ms at rate "
               f"{hedge['delay_rate']}; wasted "
               f"{hedge['wasted_pct']}% of dispatches, bar <= 15%)"),
              round(hedge["improvement"] / 2.0, 4),
              {k: v for k, v in hedge.items() if k != "improvement"})
        # the partition/kill soak: zero incorrect verdicts, only typed
        # failures, the partitioned replica re-enters (asserted inside)
        part = measure_partition()
        _emit("fleet_partition_soak_completed", part["completed"],
              (f"verified calls through a fleet whose replica r0 was "
               f"KILLED and r1 PARTITIONED mid-soak "
               f"({part['clients']} clients x {part['rounds']} rounds; "
               f"0 incorrect verdicts, 0 untyped failures, "
               f"r1 re-entries {part['r1_trips_reentries']})"),
              None,
              {k: v for k, v in part.items() if k != "completed"})
        return

    if "--kperiod" in sys.argv:
        # the K-period catch-up sweep under the CURRENT env knobs (the
        # aggregate metric is honest only next to its per-period
        # latency, which rides in extra.kperiod_sweep)
        stats = measure_kperiod()
        label = "/".join(
            f"{key.replace('GETHSHARDING_TPU_', '').lower()}={val}"
            for key, val in sorted(stats["knobs"].items())) or "defaults"
        _print_metric(
            stats["sig_rate"],
            {key: val for key, val in stats.items() if key != "sig_rate"},
            f"audit_periods K={stats['k_periods']} catch-up batch, "
            f"{label}, {stats['platform']}")
        return

    if os.environ.get("GETHSHARDING_BENCH_CPU") != "1":
        _require_accelerator()
    ensure_workload_cache()

    # the cached winner of an earlier sweep of THIS config set on THIS
    # platform skips the sweep
    best_cfg, best, cache_key = None, None, None
    try:
        with open(_cache_path()) as fh:
            cached = json.load(fh)
    except (OSError, ValueError):
        cached = {}
    if (cached.get("sweep") == _sweep_fingerprint()
            and all(key in cached for key in ("config", "platform"))):
        best_cfg, cache_key = cached["config"], cached["platform"]

    # every child below either returns stats or raises ConfigFailed,
    # which ends the run non-zero with nothing reported: a config that
    # crashed, timed out or failed its correctness gate is a failure,
    # not a row missing from the ranking
    if best_cfg is not None:
        stats = _run_config(best_cfg, extras=True)
        if stats.get("platform") == cache_key:
            best = stats
        else:
            best_cfg = None  # the winner was ranked on another platform

    if best_cfg is None:
        results = []
        sweep_start = time.monotonic()
        for i, cfg in enumerate(CONFIGS):
            if results and time.monotonic() - sweep_start > SWEEP_BUDGET_S:
                print(f"# sweep budget exhausted after {i} configs",
                      file=sys.stderr)
                break
            stats = _run_config(cfg)
            results.append((cfg, stats))
            print(f"# config {cfg} -> {stats['sig_rate']:.1f} sigs/sec "
                  f"[{stats['platform']}]", file=sys.stderr)
        best_cfg, best = max(results, key=lambda r: r[1]["sig_rate"])
        try:
            with open(_cache_path(), "w") as fh:
                json.dump({"sweep": _sweep_fingerprint(),
                           "config": best_cfg,
                           "platform": best["platform"]}, fh)
        except OSError:
            pass
        # one extra run of the winner for the config 1/2/4/5 numbers
        best = _run_config(best_cfg, extras=True)

    # label from the FULL winning config (any knob may decide the sweep)
    knobs = "/".join(
        [best_cfg.get("GETHSHARDING_TPU_LIMB_FORM", "wide"),
         best_cfg.get("GETHSHARDING_TPU_CARRY", "scan"),
         best_cfg.get("GETHSHARDING_TPU_CONV", "shift")]
        + (["pairconv-pallas"]
           if best_cfg.get("GETHSHARDING_TPU_PAIRCONV") == "pallas" else [])
        + ([f"pair-unroll-{best_cfg['GETHSHARDING_TPU_PAIR_UNROLL']}"]
           if best_cfg.get("GETHSHARDING_TPU_PAIR_UNROLL", "0") != "0"
           else [])
        + ([f"scan-unroll{best_cfg['GETHSHARDING_TPU_SCAN_UNROLL']}"]
           if best_cfg.get("GETHSHARDING_TPU_SCAN_UNROLL") else [])
        + (["norm-relaxed"]
           if best_cfg.get("GETHSHARDING_TPU_NORM") == "relaxed" else [])
        + (["pallas-norm"] if best_cfg.get("GETHSHARDING_TPU_PALLAS") == "1"
           else [])
        + (["finalexp-mega"]
           if best_cfg.get("GETHSHARDING_TPU_FINALEXP") == "mega" else [])
        + (["miller-mega"]
           if best_cfg.get("GETHSHARDING_TPU_MILLER") == "mega" else [])
        + (["agg-mega"]
           if best_cfg.get("GETHSHARDING_TPU_AGG") == "mega" else []))
    _print_metric(best["sig_rate"], best, f"{knobs}, {best['platform']}")


if __name__ == "__main__":
    main()
