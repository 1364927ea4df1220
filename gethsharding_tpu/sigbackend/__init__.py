"""Signature backends: the `--sigbackend={python,jax}` seam.

The reference routes all signature work through native code chosen at
build time (cgo libsecp256k1, bn256 assembly — SURVEY.md §2.3). Here the
same seam is a runtime-selected backend object:

- ``python``: the scalar host implementations (`crypto/secp256k1`,
  `crypto/bn256`) — always available, no accelerator required. The
  byte-exact baseline.
- ``jax``: the batched TPU kernels (`ops/secp256k1_jax`,
  `ops/bn256_jax`) — batch-first; one dispatch verifies a whole period's
  worth of signatures. Imports JAX lazily so CPU-only control-plane
  processes never initialize an accelerator backend.

Both backends implement the same API and are differential-tested against
each other (tests/test_sigbackend.py). Actors take a backend instance;
the CLI exposes ``--sigbackend``.

- ``serving-python`` / ``serving-jax``: either backend behind the
  request-coalescing serving tier (``gethsharding_tpu/serving/``) —
  concurrent small calls from many threads share device dispatches;
  the CLI's ``--serving`` flag wires the same wrapper.
- ``failover-*``: any of the above as the PRIMARY behind a circuit
  breaker with the scalar ``python`` backend as the always-sound
  fallback (``gethsharding_tpu/resilience/breaker.py``): consecutive
  device faults or watchdog timeouts trip the breaker open, calls are
  served scalar while open, and a half-open differential spot-check
  re-promotes the accelerated path only when it agrees with the
  fallback byte-for-byte.
- the soundness spot-checker
  (``gethsharding_tpu/resilience/soundness.py``, ``--soundness-rate``)
  composes between them: a drop-in wrapper re-verifying a seeded-
  random row subset of a sampled fraction of dispatches against the
  scalar reference, so a device that silently returns WRONG verdicts
  (no exception to catch) still trips the breaker via
  `SoundnessViolation` within a quantifiable number of dispatches.

Package layout (the internal DAG is enforced by the layering lint rule
through ``analysis/layers.json``'s ``internal`` block):

- ``marshal.py`` — host->limb planes, padding policy, the u16 wire.
  Pure host arithmetic; the bottom of the package.
- ``layout.py`` — device placement: single device by default, the 1-D
  ``("shard",)`` mesh under ``--mesh-devices`` /
  ``GETHSHARDING_MESH_DEVICES`` > 1 (`NamedSharding(P('shard'))` over
  `parallel/mesh.make_mesh`).
- ``cache.py`` — the resident pk-plane LRU + batch memo; sharded
  per device on mesh layouts with per-device devscope owners.
- ``dispatch.py`` — `JaxSigBackend`: jit/pjit launch, DeviceTimer,
  compile_span, the wire ledger, and the one-collective mesh audit
  step. Lazily imported (PEP 562) so this package stays importable on
  accelerator-free control planes.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

from gethsharding_tpu.crypto import bn256 as bls
from gethsharding_tpu.crypto import secp256k1 as ecdsa
# the padding policy lives in marshal.py; re-exported here because the
# serving layer (and tests) import it from the package root
from gethsharding_tpu.sigbackend.marshal import bucket_size
from gethsharding_tpu.utils.hexbytes import Address20


class VerdictFuture:
    """Handle on an in-flight committee verification.

    The jax backend's device dispatch is asynchronous: `result()` is
    where the verdict is pulled to the host (`np.asarray`), so a caller
    that submits period N+1 (or does any other host work) between
    submit and `result()` overlaps its host time with N's device
    execution. `concurrent.futures.Future`-compatible on the one method
    the notary uses (`result`), so the serving tier's real futures are
    drop-in."""

    __slots__ = ("_finalize", "_value", "_done")

    def __init__(self, finalize):
        self._finalize = finalize
        self._value = None
        self._done = False

    def result(self, timeout=None):
        if not self._done:
            self._value = self._finalize()
            self._done = True
            self._finalize = None  # drop the staged buffers
        return self._value

    def done(self) -> bool:
        return self._done


class SigBackend:
    """Batch signature operations used by the consensus hot loops."""

    name = "abstract"

    def ecrecover_addresses(self, digests: Sequence[bytes],
                            sigs65: Sequence[bytes]) -> List[Optional[Address20]]:
        """Recover the signer address per (32-byte digest, 65-byte [R||S||V])
        pair; None where the signature is invalid."""
        raise NotImplementedError

    def bls_verify_aggregates(
            self,
            messages: Sequence[bytes],
            agg_sigs: Sequence[bls.G1Point],
            agg_pks: Sequence[bls.G2Point]) -> List[bool]:
        """Verify one aggregate committee vote per message."""
        raise NotImplementedError

    def bls_verify_committees(
            self,
            messages: Sequence[bytes],
            sig_rows: Sequence[Sequence[bls.G1Point]],
            pk_rows: Sequence[Sequence[bls.G2Point]],
            pk_row_keys: Optional[Sequence] = None) -> List[bool]:
        """Aggregate each row's vote signatures + voter pubkeys and verify
        the aggregate against the row's message. The batch form of the
        whole committee check: with the jax backend both the aggregation
        (masked projective tree reduction) and the pairing run in ONE
        device dispatch. Empty rows are rejections (an empty committee
        proves nothing). `pk_row_keys` (optional, one hashable per row,
        e.g. the wire encoding) lets a backend cache the marshalled
        pubkey rows — keys MUST uniquely determine the row's points."""
        raise NotImplementedError

    def bls_verify_committees_async(
            self,
            messages: Sequence[bytes],
            sig_rows: Sequence[Sequence[bls.G1Point]],
            pk_rows: Sequence[Sequence[bls.G2Point]],
            pk_row_keys: Optional[Sequence] = None) -> VerdictFuture:
        """`bls_verify_committees` returning a verdict future instead of
        blocking on the host pull. The jax backend stages and launches
        the device dispatch before returning, so the caller marshals the
        NEXT batch while this one executes on device; scalar backends
        compute eagerly and return a resolved future (same contract, no
        overlap). Verdicts are bit-identical to the sync form."""
        out = self.bls_verify_committees(messages, sig_rows, pk_rows,
                                         pk_row_keys=pk_row_keys)
        future = VerdictFuture(lambda: out)
        future.result()  # scalar path: already computed; mark resolved
        return future

    def das_verify_samples(
            self,
            chunks: Sequence[bytes],
            indices: Sequence[int],
            proofs: Sequence[Sequence[bytes]],
            roots: Sequence[bytes]) -> List[bool]:
        """Verify one DAS sample per row: does `chunks[i]` sit at leaf
        `indices[i]` of the commitment tree rooted at `roots[i]`, per
        the sibling path `proofs[i]`? (das/proofs.py defines the leaf
        as the chunk's netstore address, so the per-row work is a full
        BMT recompute + path fold — keccak lanes.) Malformed rows
        (wrong chunk size, bad index, over-deep or ragged proofs) are
        False, never an exception: a hostile sample response must cost
        a verdict, not a batch. The jax backend runs the whole batch as
        ONE fixed-shape keccak dispatch over samples × shards."""
        raise NotImplementedError

    def das_verify_multiproofs(
            self,
            commitments: Sequence[bytes],
            index_rows: Sequence[Sequence[int]],
            eval_rows: Sequence[Sequence[int]],
            proofs: Sequence[bytes],
            ns: Sequence[int]) -> List[bool]:
        """Verify one DAS polynomial multiproof per row: does the
        64-byte G1 point `proofs[i]` open the 64-byte commitment
        `commitments[i]` to the claimed chunk-value evaluations
        `eval_rows[i]` at the sampled index set `index_rows[i]`, over
        a degree-<ns[i] evaluation domain? (das/pcs.py defines the
        scheme; one row = one sampled collation, the proof constant-
        size however many chunks the row samples.) Malformed rows (bad
        shapes, undecodable or off-curve points, duplicate or out-of-
        domain indices) are False, never an exception. The jax backend
        folds the whole batch into ONE two-pair pairing dispatch on
        the existing bn256 kernel."""
        raise NotImplementedError


class PythonSigBackend(SigBackend):
    """Scalar host crypto — parity baseline."""

    name = "python"

    def ecrecover_addresses(self, digests, sigs65):
        out: List[Optional[Address20]] = []
        for digest, sig in zip(digests, sigs65):
            try:
                signature = ecdsa.Signature.from_bytes65(bytes(sig))
                out.append(ecdsa.ecrecover_address(bytes(digest), signature))
            except (ValueError, AssertionError):
                out.append(None)
        return out

    def bls_verify_aggregates(self, messages, agg_sigs, agg_pks):
        return [
            bls.bls_verify(bytes(m), s, pk)
            for m, s, pk in zip(messages, agg_sigs, agg_pks)
        ]

    def bls_verify_committees(self, messages, sig_rows, pk_rows,
                              pk_row_keys=None):
        return [
            bls.bls_verify_aggregate(
                bytes(m), bls.bls_aggregate_sigs(sigs), list(pks))
            for m, sigs, pks in zip(messages, sig_rows, pk_rows)
        ]

    def das_verify_samples(self, chunks, indices, proofs, roots):
        # lazy import: the das package is optional workload surface,
        # not a dependency of every scalar control plane
        from gethsharding_tpu.das.proofs import verify_samples

        return verify_samples(chunks, indices, proofs, roots)

    def das_verify_multiproofs(self, commitments, index_rows, eval_rows,
                               proofs, ns):
        # lazy for the same reason as das_verify_samples
        from gethsharding_tpu.das.poly_proofs import verify_multiproofs

        return verify_multiproofs(commitments, index_rows, eval_rows,
                                  proofs, ns)


def _jax_factory() -> SigBackend:
    """Factory for the accelerated backend. Lazy import of dispatch.py
    (which eagerly imports layout/cache/marshal): requesting 'jax' is
    the moment a process opts into the accelerator plane."""
    from gethsharding_tpu.sigbackend.dispatch import JaxSigBackend

    return JaxSigBackend()


def _serving_factory(inner_name: str):
    """Factory for the serving-tier wrappers ('serving-python' /
    'serving-jax'): the wrapped backend stays the process singleton, the
    wrapper adds the micro-batching admission tier in front of it. Lazy
    import: control planes that never serve must not pay for the
    serving threads module."""
    def build() -> SigBackend:
        from gethsharding_tpu.serving.backend import ServingSigBackend

        return ServingSigBackend(get_backend(inner_name))

    return build


def _failover_factory(primary_name: str):
    """Factory for the breaker-guarded wrappers ('failover-<primary>'):
    the primary stays the registry singleton; the scalar python backend
    is the always-available fallback. Lazy import: only nodes that opt
    into failover load the resilience layer."""
    def build() -> SigBackend:
        from gethsharding_tpu.resilience.breaker import FailoverSigBackend

        return FailoverSigBackend(get_backend(primary_name),
                                  get_backend("python"))

    return build


_BACKENDS = {
    "python": PythonSigBackend,
    "jax": _jax_factory,
    "serving-python": _serving_factory("python"),
    "serving-jax": _serving_factory("jax"),
    "failover-python": _failover_factory("python"),
    "failover-jax": _failover_factory("jax"),
    "failover-serving-python": _failover_factory("serving-python"),
    "failover-serving-jax": _failover_factory("serving-jax"),
}
_cache: dict = {}


def get_backend(name: str = "python") -> SigBackend:
    """Backend registry: 'python' (scalar host), 'jax' (batched TPU),
    the 'serving-*' coalescing wrappers, or the 'failover-*'
    breaker-guarded wrappers over any of them."""
    if name not in _BACKENDS:
        raise ValueError(
            f"unknown sigbackend {name!r}; choose from {sorted(_BACKENDS)}")
    if name not in _cache:
        _cache[name] = _BACKENDS[name]()
    return _cache[name]


def device_record_of(backend) -> Optional[dict]:
    """The device record (platform / device_kind / count, as JAX reported
    them to `JaxSigBackend`) of the accelerated backend under `backend`,
    found by walking the wrapper chain (`.inner` hops through the
    serving / soundness / chaos / failover faces). None when the
    composition bottoms out in a scalar backend — which is how a client
    tells a replica that answers from a device from one that does not."""
    probe, hops = backend, 0
    while probe is not None and hops < 8:
        record = getattr(probe, "device_record", None)
        if record is not None:
            return dict(record)
        probe, hops = getattr(probe, "inner", None), hops + 1
    return None


def __getattr__(name: str):
    # PEP 562: `from gethsharding_tpu.sigbackend import JaxSigBackend`
    # keeps working without this package eagerly importing dispatch.py
    # (and through it the kernels) on accelerator-free control planes
    if name == "JaxSigBackend":
        from gethsharding_tpu.sigbackend.dispatch import JaxSigBackend

        return JaxSigBackend
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
