"""Microbenchmark registry: the CPU-quick workloads the gate watches.

The benchmark (`benchmark/run.py`) needs a chip and minutes of wall
clock; a refactor gate needs something a CI step can run in seconds,
anywhere, and still catch "the sigbackend split cost
10% on the host paths". These microbenches are that tier: small,
deterministic, host-only workloads registered with their gated metric
directions, each run appended to the ledger through the one writer so
the regression gate (`perfwatch/gate.py`) can diff them against their
own rolling history.

Timing discipline: one warm-up call, then `repeats` timed calls with
the MINIMUM wall taken (the standard microbenchmark estimator — the
min is the least noisy location statistic for a lower-bounded timing
distribution); derived rates come from the same minimum.

Injection (`GETHSHARDING_PERFWATCH_INJECT="name:factor[,...]"` or the
`inject=` argument): the recorded timing metrics of the named bench
are scaled by `factor` (rates divided) and the record is stamped
``injected`` — the drill the perfwatch smoke uses to prove the gate
actually trips, without faking an unlabeled measurement.
"""

from __future__ import annotations

import os
import time
from typing import Callable, Dict, List, Optional

from gethsharding_tpu.perfwatch.ledger import Ledger

# name -> (fn, repeats, quick): fn() -> flat numeric metrics dict
# (must include wall_s; *_per_s metrics are gated higher-is-better)
MICROBENCHES: Dict[str, tuple] = {}


def microbench(name: str, repeats: int = 3, quick: bool = True):
    """Register a microbenchmark; `fn()` returns its metrics dict."""
    def wrap(fn: Callable[[], Dict[str, float]]):
        MICROBENCHES[name] = (fn, repeats, quick)
        return fn

    return wrap


def parse_inject(spec: Optional[str] = None) -> Dict[str, float]:
    """``"keccak_256x64:1.3,ecrecover_scalar_8:2"`` -> {name: factor}."""
    if spec is None:
        spec = os.environ.get("GETHSHARDING_PERFWATCH_INJECT", "")
    out: Dict[str, float] = {}
    for part in filter(None, (p.strip() for p in spec.split(","))):
        if ":" not in part:
            raise ValueError(
                f"bad inject entry {part!r}: expected name:factor")
        name, factor = part.rsplit(":", 1)
        out[name] = float(factor)
    return out


def run(name: str, ledger: Optional[Ledger] = None,
        inject: Optional[Dict[str, float]] = None) -> dict:
    """Run one registered microbench and append its ledger record."""
    if name not in MICROBENCHES:
        raise ValueError(f"unknown microbench {name!r}; "
                         f"choose from {sorted(MICROBENCHES)}")
    from gethsharding_tpu.perfwatch.timer import suspect_count

    fn, repeats, _quick = MICROBENCHES[name]
    inject = parse_inject() if inject is None else inject
    suspects_before = suspect_count()
    fn()  # warm-up: first-call import/alloc cost is not the workload
    best: Optional[Dict[str, float]] = None
    for _ in range(max(1, repeats)):
        mets = fn()
        if best is None or mets["wall_s"] < best["wall_s"]:
            best = dict(mets)
    factor = inject.get(name)
    extra: Dict[str, object] = {}
    if factor is not None:
        for key in list(best):
            # rates FIRST: "_per_s" also ends with "_s", and a rate
            # scaled the timing way would record an injected slowdown
            # as a speedup
            if key.endswith("_per_s"):
                best[key] /= factor
            elif key.endswith(("_s", "_ms", "_us")):
                best[key] *= factor
        extra["injected"] = factor
    suspects = suspect_count() - suspects_before
    record = {
        "workload": f"micro/{name}",
        "backend": "host",
        "platform": "host",
        "metrics": {k: round(float(v), 9) for k, v in best.items()},
        "extra": extra,
        "source": "micro",
        "suspects": suspects,
        "valid": suspects == 0,
    }
    return (ledger or Ledger()).append(record)


def run_suite(ledger: Optional[Ledger] = None, quick: bool = True,
              names: Optional[List[str]] = None,
              inject: Optional[Dict[str, float]] = None) -> List[dict]:
    """Run the (quick) suite in registration order; returns the
    appended records."""
    ledger = ledger or Ledger()
    out = []
    for name, (_fn, _r, is_quick) in MICROBENCHES.items():
        if names is not None and name not in names:
            continue
        if quick and not is_quick:
            continue
        out.append(run(name, ledger=ledger, inject=inject))
    return out


# == the built-in CPU-quick suite ==========================================
# All host-only (no accelerator, no jax import): runnable in any CI
# container in a few seconds, covering the host-side hot paths a
# sigbackend/serving refactor is most likely to slow down — keccak
# hashing, scalar signature recovery, the bucket padding policy, and
# the serving coalescing overhead.


_ECRECOVER_CASES: Optional[list] = None


def _ecrecover_cases(n: int = 8) -> list:
    """Deterministic (digest, sig65) pairs, built once per process."""
    global _ECRECOVER_CASES
    if _ECRECOVER_CASES is None:
        from gethsharding_tpu.crypto import secp256k1 as ecdsa
        from gethsharding_tpu.crypto.keccak import keccak256

        cases = []
        for i in range(n):
            priv = int.from_bytes(keccak256(b"perfwatch-%d" % i),
                                  "big") % ecdsa.N
            digest = keccak256(b"perfwatch-msg-%d" % i)
            cases.append((digest, ecdsa.sign(digest, priv).to_bytes65()))
        _ECRECOVER_CASES = cases
    return _ECRECOVER_CASES


@microbench("clock_spin_5ms")
def _bench_clock_spin() -> Dict[str, float]:
    """Deterministic 5 ms monotonic busy-spin — the timing REFERENCE
    bench. Its wall is set by the clock, not by the host's load (the
    real workload benches drift ~20% with CPU state on a shared box),
    so the injection drill and the gate's own plumbing can be
    validated without inheriting machine noise: a labeled 1.3x on this
    bench MUST trip, a clean rerun MUST NOT."""
    t0 = time.perf_counter()
    deadline = t0 + 0.005
    while time.perf_counter() < deadline:
        pass
    return {"wall_s": time.perf_counter() - t0}


@microbench("keccak_256x64")
def _bench_keccak() -> Dict[str, float]:
    """64 keccak256 hashes of 256-byte messages — the DAS/BMT and
    digest hot primitive."""
    from gethsharding_tpu.crypto.keccak import keccak256

    msgs = [bytes([i % 251]) * 256 for i in range(64)]
    t0 = time.perf_counter()
    for m in msgs:
        keccak256(m)
    wall = time.perf_counter() - t0
    return {"wall_s": wall, "hashes_per_s": len(msgs) / wall}


@microbench("ecrecover_scalar_8")
def _bench_ecrecover() -> Dict[str, float]:
    """8 scalar host ecrecovers through PythonSigBackend — the
    fallback/differential path every resilience layer leans on."""
    from gethsharding_tpu.sigbackend import PythonSigBackend

    cases = _ecrecover_cases()
    backend = PythonSigBackend()
    digests = [d for d, _ in cases]
    sigs = [s for _, s in cases]
    t0 = time.perf_counter()
    out = backend.ecrecover_addresses(digests, sigs)
    wall = time.perf_counter() - t0
    assert all(a is not None for a in out), "workload must recover"
    return {"wall_s": wall, "rows_per_s": len(cases) / wall}


@microbench("bucket_policy_10k")
def _bench_bucket() -> Dict[str, float]:
    """10k bucket_size calls — the padding policy sits on every
    dispatch and every serving flush decision."""
    from gethsharding_tpu.sigbackend import bucket_size

    t0 = time.perf_counter()
    acc = 0
    for n in range(1, 10_001):
        acc += bucket_size(n)
    wall = time.perf_counter() - t0
    assert acc > 0
    return {"wall_s": wall, "calls_per_s": 10_000 / wall}


@microbench("serving_coalesce_16")
def _bench_serving() -> Dict[str, float]:
    """16 single-row ecrecover requests from 4 threads through the
    serving tier (python inner) — the coalescing admission overhead,
    end to end."""
    import threading

    from gethsharding_tpu.serving import ServingConfig, ServingSigBackend
    from gethsharding_tpu.sigbackend import PythonSigBackend

    cases = _ecrecover_cases()
    serving = ServingSigBackend(PythonSigBackend(),
                                ServingConfig(flush_us=200.0))
    try:
        serving.ecrecover_addresses([], [])  # spin up the threads
        errors: list = []

        def client(c: int) -> None:
            for r in range(4):
                digest, sig = cases[(c * 4 + r) % len(cases)]
                if serving.ecrecover_addresses([digest], [sig]) == [None]:
                    errors.append((c, r))

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(4)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        assert not errors, errors
    finally:
        serving.close()
    return {"wall_s": wall, "requests_per_s": 16 / wall}
