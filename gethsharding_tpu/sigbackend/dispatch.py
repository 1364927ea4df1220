"""Device dispatch: jit/pjit launch, DeviceTimer, wire ledger.

`JaxSigBackend` — the batched accelerator backend — composes the other
three submodules: `marshal` builds the host limb planes, `layout`
decides where they land (single device, or the 1-D shard mesh), and
`cache` (mixed in) keeps the recurring pk planes device-resident.
This module owns what remains: the jitted kernels, the compile-cache
bookkeeping (`_note_shape` + `compile_span`), the executables held by
(op, shape) with the store behind them (`_run`, `execstore.py`), the
`DeviceTimer` attribution of every dispatch, and the per-dispatch wire
ledger.

The mesh committee path (`_committee_submit_mesh`) is the tentpole:
the whole period audit runs as ONE pjit'd step — a `shard_map` whose
only cross-device traffic is the vote-total `psum` (asserted per
compiled executable via `layout.count_collectives` over the AOT HLO).
Everything else — verdict plane, pk planes, fresh-per-period planes —
stays strictly device-local under `NamedSharding(P('shard'))`.

Never import this module eagerly: `sigbackend/__init__` exposes
`JaxSigBackend` lazily (PEP 562) so CPU-only control planes never
initialize an accelerator backend.
"""

from __future__ import annotations

import os

from gethsharding_tpu import metrics, tracing
from gethsharding_tpu.crypto import bn256 as bls
from gethsharding_tpu.crypto import secp256k1 as ecdsa
# DeviceTimer is THE timing primitive of every dispatch path below: it
# forces a real device->host pull, self-checks block-vs-pull divergence
# into `perfwatch/timer_suspect`, and feeds the
# sig/{marshal_time,device_time} rollups; RECORDER keeps the last-N
# dispatch wire ledgers for the flight recorder's post-mortem bundles
from gethsharding_tpu.perfwatch import RECORDER, DeviceTimer
from gethsharding_tpu.sigbackend import SigBackend, VerdictFuture
from gethsharding_tpu.sigbackend import layout as layout_mod
from gethsharding_tpu.sigbackend import marshal
from gethsharding_tpu.sigbackend.cache import ResidentPkCache
from gethsharding_tpu.sigbackend.execstore import ExecutableStore
from gethsharding_tpu.sigbackend.marshal import bucket_size

# the host stages of the committee and the DAS sample dispatches, the
# parts of DeviceTimer's sig/marshal_time (host_marshal + transfer) and
# sig/device_time (launch, then perfwatch/timer.py's block and pull)
_T_HOST_MARSHAL = metrics.timer("sig/host_marshal_time")
_T_TRANSFER = metrics.timer("sig/transfer_time")
_T_LAUNCH = metrics.timer("sig/launch_time")


class JaxSigBackend(ResidentPkCache, SigBackend):
    """Batched accelerator kernels; one dispatch per batch."""

    name = "jax"

    def __init__(self, mesh_devices=None, exec_store=None):
        import jax  # lazy: only sig-verifying processes touch the backend
        import jax.numpy as jnp

        from gethsharding_tpu.ops import device

        # building this backend is the moment a process opts into the
        # accelerator plane, whatever its entry point (chain_server, the
        # node CLI, bench, tests): the compile cache is placed and the
        # devices are resolved ONCE — platform / device_kind / count as
        # JAX reports them, refusing an undeclared CPU fallback
        # (ops/device.py) before any kernel is traced
        self.device_record = device.device_record()

        from gethsharding_tpu.ops import bn256_jax, secp256k1_jax

        self._jax = jax
        self._jnp = jnp
        self._bn = bn256_jax
        self._sec = secp256k1_jax
        self._recover = jax.jit(secp256k1_jax.ecrecover_batch)
        self._bls = jax.jit(bn256_jax.bls_verify_aggregate_batch)
        self._bls_committee = jax.jit(
            bn256_jax.bls_aggregate_verify_committee_batch)
        # GETHSHARDING_TPU_WIRE=u16: ship limb planes over the
        # host->device link as uint16 (12-bit limbs waste 20 of 32 bits;
        # halves the audit's transfer bytes) and widen to int32 ON
        # DEVICE before the kernel — value-identical, the wire format
        # never reaches the arithmetic
        self._wire_u16 = os.environ.get("GETHSHARDING_TPU_WIRE") == "u16"
        self._wire = "u16" if self._wire_u16 else "i32"

        def _committee_u16(hx, hy, sx, sy, sm, px, py, pm, hok):
            i32 = jnp.int32
            return bn256_jax.bls_aggregate_verify_committee_batch(
                hx.astype(i32), hy.astype(i32), sx.astype(i32),
                sy.astype(i32), sm, px.astype(i32), py.astype(i32),
                pm, hok)

        self._bls_committee_u16 = jax.jit(_committee_u16)
        # GETHSHARDING_PRECOMP: fixed-base pairing precomputation
        # (default on). The committee path consumes device-resident
        # Miller line tables keyed by pk_row_key instead of re-running
        # the fixed-argument point arithmetic every dispatch — a cold
        # row pays one precompute dispatch, every warm audit ships zero
        # G2 bytes AND skips the point-arithmetic half of the Miller
        # loop. 0 restores today's recompute path.
        precomp = os.environ.get("GETHSHARDING_PRECOMP", "1")
        if precomp not in ("0", "1"):
            raise ValueError(
                f"GETHSHARDING_PRECOMP={precomp!r}: want 0 or 1")
        self._precomp = precomp == "1"

        def _precompute_planes(px, py, pm):
            i32 = jnp.int32
            tabs, infs = bn256_jax.precompute_g2_lines(
                px.astype(i32), py.astype(i32), pm)
            # cut INSIDE the program: one buffer a row is what the LRU
            # stores, and an eager `tabs[j]` on the host is three
            # dispatches a key (PERF.md section 6, PR 34)
            rows = range(px.shape[0])
            return (tuple(tabs[j] for j in rows),
                    tuple(infs[j] for j in rows))

        # one precompute jit serves every layout: committed inputs keep
        # the dispatch on the owning device (mesh shards included); the
        # astype is a no-op on the i32 wire
        self._precompute = jax.jit(_precompute_planes)

        def _stack_lines(tabs, infs):
            return jnp.stack(tabs), jnp.stack(infs)

        # its inverse: one table and one flag a row (zero row, hit,
        # miss) into the (B, L, 3, 2, nl) and (B,) planes of the
        # table-fed verify, in one launch of 2 x B arguments
        self._stack_lines = jax.jit(_stack_lines)

        def _precomp_full(hx, hy, sx, sy, sm, tab, inf, hok, gen):
            i32 = jnp.int32
            return bn256_jax.bls_verify_committee_precomp_batch(
                hx.astype(i32), hy.astype(i32), sx.astype(i32),
                sy.astype(i32), sm, tab, inf, hok, gen_lines=gen)

        # ONE program over the whole bucket, as the recompute path has:
        # rows lie on the lane axis and a step is bound by the number of
        # operations, so a bucket cut into lane blocks pays each block's
        # cost again (PERF.md section 6, PR 31)
        self._precomp_full = jax.jit(_precomp_full)
        # the multiproof check with both MSMs on the device, and the
        # builder of the SRS's fixed-base tables it reads (`_srs_tables`)
        self._das_poly = jax.jit(bn256_jax.das_poly_verify_batch)
        # the same program partitioned over a mesh keeps the XLA
        # pairing, like the mesh's committee steps
        self._das_poly_mesh = jax.jit(
            lambda *planes: bn256_jax.das_poly_verify_batch(
                *planes, pallas=False))
        self._das_poly_tables = jax.jit(bn256_jax.das_poly_tables)
        # the backend is a process-wide singleton shared by every actor
        # thread (get_backend caches instances): all cache structures
        # are lock-guarded (cache.py)
        self._init_pk_caches()
        import threading

        self._srs_lock = threading.Lock()
        # the multiproof rows whose two MSMs the device summed (a
        # malformed row is decided on the host)
        self._m_msm_rows = metrics.counter("das/poly/device_msm_rows")
        # the real rows of a dispatch whose program runs its pairing
        # check in the Pallas kernels (bn256_jax.pairing_in_pallas): 0
        # on the CPU and on a mesh
        self._m_pallas_rows = metrics.counter("sig/pairing/pallas_rows")
        self._m_wire_bytes = metrics.counter("jax/wire/bytes")
        # the G2 part of it: pk planes shipped cold (0 on a warm audit)
        self._m_g2_bytes = metrics.counter("jax/wire/g2_bytes")
        # the chunk plane's part of jax/wire/bytes on the DAS sample path
        self._m_das_chunk_bytes = metrics.counter("das/wire/chunk_bytes")
        self._m_pk_hit_bytes = metrics.counter("jax/wire/pk_device_hit_bytes")
        # device-time attribution rollups (sig/{marshal_time,
        # device_time}) are fed by the perfwatch DeviceTimer each
        # dispatch path below constructs — one timing scheme, with the
        # block-vs-pull self-check built in
        # compile-cache visibility: jax.jit compiles once per argument
        # SHAPE, and every padded bucket this process has not dispatched
        # before is a fresh XLA compile (seconds to minutes). Tracking
        # (op, bucket-shape) first-sightings makes recompile storms —
        # e.g. unbucketed traffic widening the shape set — visible as
        # counters and span tags instead of mystery latency spikes.
        self._shape_seen: set = set()
        self._shape_lock = threading.Lock()
        self._m_shape_hit = metrics.counter("jax/compile_cache/hits")
        self._m_shape_miss = metrics.counter("jax/compile_cache/misses")
        from gethsharding_tpu import devscope

        self._compiles = devscope.COMPILES
        # THE layout decision: single-device unless the constructor or
        # GETHSHARDING_MESH_DEVICES asks for a mesh. Everything below
        # branches on `self._layout.is_mesh`, nothing else.
        self._layout = layout_mod.DeviceLayout(
            layout_mod.mesh_devices_requested(mesh_devices))
        # the single-device programs' executables, held by (op, shape)
        # with a disk behind them (execstore.py): a fresh shape loads
        # its program by shape and traces nothing. Engaged by what the
        # device record says (never on the CPU), or by the store a test
        # hands in; the mesh keeps `_mesh_exec`, in-process only.
        if self._layout.is_mesh:
            exec_store = None
        elif exec_store is None:
            exec_store = ExecutableStore.for_device(self.device_record)
        self._exec_store = exec_store
        self._held: dict = {}
        if self._layout.is_mesh:
            # per-device cache shards + their devscope census owners
            self._init_mesh_shards(self._layout)
            # AOT executables per (bucket, width, wire) — lowering once
            # through .lower().compile() yields BOTH the executable and
            # its HLO text, so the one-collective transfer-ledger check
            # costs no second compilation
            self._mesh_exec: dict = {}
            self._mesh_collectives: dict = {}
            # the newest compiled step's count, for remote readers
            # (shard_metrics): in-process readers use `last_mesh`
            self._g_mesh_collectives = metrics.gauge("jax/mesh/collectives")
            from jax import shard_map
            from jax.sharding import PartitionSpec

            mesh = self._layout.mesh
            spec = self._layout.shard_spec()
            axis_names = mesh.axis_names

            def _mesh_step(hx, hy, sx, sy, sm, px, py, pm, hok):
                # the ONE pjit'd audit step: each device verifies its
                # slab of committees (astype is a no-op on the i32
                # wire), then the vote total — the ONLY cross-device
                # value — is psum'd. Everything else stays local. The
                # pairing is XLA's here and in the twin below: a
                # `pallas_call` inside `shard_map` fails at trace
                i32 = jnp.int32
                ok = bn256_jax.bls_aggregate_verify_committee_batch(
                    hx.astype(i32), hy.astype(i32), sx.astype(i32),
                    sy.astype(i32), sm, px.astype(i32),
                    py.astype(i32), pm, hok, pallas=False)
                votes = jax.lax.psum(jnp.sum(ok.astype(i32)), axis_names)
                return ok, votes

            self._bls_committee_mesh = jax.jit(shard_map(
                _mesh_step, mesh=mesh, in_specs=(spec,) * 9,
                out_specs=(spec, PartitionSpec())))

            def _mesh_step_precomp(hx, hy, sx, sy, sm, tab, inf, hok,
                                   gen):
                # the precomp twin of the ONE pjit'd audit step: line
                # tables arrive pre-sharded from the per-device cache
                # shards, the replicated generator table rides along,
                # and the vote-total psum stays the only collective
                i32 = jnp.int32
                ok = bn256_jax.bls_verify_committee_precomp_batch(
                    hx.astype(i32), hy.astype(i32), sx.astype(i32),
                    sy.astype(i32), sm, tab, inf, hok, gen_lines=gen,
                    pallas=False)
                votes = jax.lax.psum(jnp.sum(ok.astype(i32)), axis_names)
                return ok, votes

            self._bls_committee_mesh_precomp = jax.jit(shard_map(
                _mesh_step_precomp, mesh=mesh,
                in_specs=(spec,) * 8 + (PartitionSpec(),),
                out_specs=(spec, PartitionSpec())))
        # the G2-generator line table: precomputed at import (host),
        # shipped ONCE at construction and passed into every precomp
        # executable as an argument — an embedded constant would
        # re-materialize per compiled shape. Censused by the resident
        # owners (cache.py) so devscope attribution stays drift-free.
        if self._precomp:
            if self._layout.is_mesh:
                from jax.sharding import NamedSharding, PartitionSpec

                self._gen_lines_mesh = jax.device_put(
                    bn256_jax.generator_line_table(),
                    NamedSharding(self._layout.mesh, PartitionSpec()))
            else:
                self._gen_lines_dev = jnp.asarray(
                    bn256_jax.generator_line_table())
        # device-memory attribution: the resident pk-plane LRU (and on
        # mesh layouts each per-device shard) registers as a devscope
        # census owner — cache.py holds the weakref plumbing
        self._register_census_owner()

    def _note_shape(self, op: str, *shape) -> bool:
        """Count a dispatch against the per-shape compile cache; True
        when this (op, shape) is NEW to the process (an XLA compile).
        Fresh sightings also feed the devscope recompile-storm window
        (compilewatch.py) — hits cost one extra early-returning call."""
        key = (op,) + shape
        with self._shape_lock:
            fresh = key not in self._shape_seen
            if fresh:
                self._shape_seen.add(key)
        (self._m_shape_miss if fresh else self._m_shape_hit).inc()
        compiles = getattr(self, "_compiles", None)
        if compiles is None:
            # partially-built instances (tests stub the tracking state
            # via __new__) self-heal onto the process watch; idempotent
            from gethsharding_tpu import devscope

            compiles = self._compiles = devscope.COMPILES
        compiles.saw(op, shape, fresh)
        return fresh

    # None on the CPU, on a mesh and on partially-built test instances:
    # `_run` is then the jitted call it always was
    _exec_store = None

    def _run(self, op: str, shape: tuple, fn, args, booking):
        """Launch the jitted `fn` at (op, shape), inside the caller's
        `compile_span` (whose `booking` is None but on a fresh shape).
        With a store, through the executable held for (op, shape): the
        first launch loads it by shape, or lowers and compiles once and
        stores it, and every later one calls what is held. A loaded
        executable that refuses its first call costs a trace, never a
        request."""
        store = self._exec_store
        if store is None:
            return fn(*args)
        key = (op,) + tuple(shape)
        exe = self._held.get(key)
        if exe is not None:
            return exe(*args)
        # `booking` may be None here: a second thread at a shape whose
        # first launch has not come back yet finds (or makes) its own
        booking = {} if booking is None else booking
        exe = store.executable(op, shape, fn, args, booking)
        try:
            out = exe(*args)
        except Exception:  # noqa: BLE001 - only a loaded one is retried
            if booking.get("source") != "store":
                raise
            store.refused(op, shape, args)
            booking.update(source="traced")
            exe = store.trace(op, shape, fn, args)
            out = exe(*args)
        self._held[key] = exe
        return out

    # the module-level bucket_size, kept as a staticmethod so kernel
    # call sites read as "this backend's padding policy"
    _bucket = staticmethod(bucket_size)

    # the device-resident G2-generator line table (single-device /
    # mesh-replicated) — None when GETHSHARDING_PRECOMP=0 or on
    # partially-built test instances
    _gen_lines_dev = None
    _gen_lines_mesh = None

    def ecrecover_addresses(self, digests, sigs65):
        import numpy as np

        jnp = self._jnp
        n = len(digests)
        if n == 0:
            return []
        dt = DeviceTimer("ecrecover")
        bucket = self._bucket(n)
        fresh = self._note_shape("ecrecover", bucket)
        pad = bucket - n
        # the three stages of every device operation (`das_verify_samples`)
        with tracing.stage("sig/host_marshal_time", _T_HOST_MARSHAL):
            sigs, valid, host_rows = [], [], []
            for i, sig in enumerate(sigs65):
                sig = bytes(sig)
                if len(sig) == 65 and sig[64] in (0, 1):
                    sigs.append(ecdsa.Signature.from_bytes65(sig))
                    valid.append(True)
                else:
                    if len(sig) == 65 and sig[64] in (2, 3):
                        # rare r+n overflow recids: scalar host fallback
                        # keeps exact RecoverPubkey parity
                        host_rows.append(i)
                    sigs.append(ecdsa.Signature(r=1, s=1, v=0))  # placeholder
                    valid.append(False)
            sigs.extend([ecdsa.Signature(r=1, s=1, v=0)] * pad)
            valid.extend([False] * pad)
            e = self._sec.hashes_to_limbs(
                [bytes(d) for d in digests] + [b"\x00" * 32] * pad)
            r, s, v = self._sec.sigs_to_limbs(sigs)
        with tracing.stage("sig/transfer_time", _T_TRANSFER):
            args = tuple(jnp.asarray(p)
                         for p in (e, r, s, v, np.asarray(valid)))
        dt.dispatched()  # marshal (incl. transfer staging) closes here
        launch = tracing.stage("sig/launch_time", _T_LAUNCH,
                               ctx=dt.span_ctx)
        # compile_span: a fresh shape's launch wall (trace + XLA compile
        # + enqueue) lands in the devscope compile ledger; on hits this
        # is one branch
        with self._compiles.compile_span("ecrecover", (bucket,),
                                         fresh) as booking, launch:
            qx, qy, ok = self._run("ecrecover", (bucket,), self._recover,
                                   args, booking)
        # the checked pull on `ok` is the dispatch barrier (block-vs-pull
        # self-checked); limbs_to_pubkeys then pulls the sibling buffers
        # of the SAME computation, so the device phase closes only after
        # the dispatch has actually executed and materialized. The host
        # `ok` is passed through — pulling it twice would add a second
        # device->host round trip per dispatch.
        ok_host = dt.pull(ok)
        pubs = self._sec.limbs_to_pubkeys(qx, qy, ok_host)[:n]
        dt.done()
        dt.record_span("jax/ecrecover_dispatch", rows=n, bucket=bucket,
                       compile="miss" if fresh else "hit")
        out = [ecdsa.pubkey_to_address(p) if p is not None else None
               for p in pubs]
        for i in host_rows:
            try:
                out[i] = ecdsa.ecrecover_address(
                    bytes(digests[i]),
                    ecdsa.Signature.from_bytes65(bytes(sigs65[i])))
            except (ValueError, AssertionError):
                out[i] = None
        return out

    def bls_verify_aggregates(self, messages, agg_sigs, agg_pks):
        jnp = self._jnp
        n = len(messages)
        if n == 0:
            return []
        dt = DeviceTimer("bls_aggregate")
        bucket = self._bucket(n)
        fresh = self._note_shape("bls_aggregate", bucket)
        pad = bucket - n
        with tracing.stage("sig/host_marshal_time", _T_HOST_MARSHAL):
            hashes = ([bls.hash_to_g1(bytes(m)) for m in messages]
                      + [None] * pad)
            hx, hy, hok = self._bn.g1_to_limbs(hashes)
            sx, sy, sok = self._bn.g1_to_limbs(list(agg_sigs) + [None] * pad)
            pkx, pky, pok = self._bn.g2_to_limbs(
                list(agg_pks) + [None] * pad)
            # infinity signature/key is an outright rejection (scalar
            # parity)
            valid = hok & sok & pok
        with tracing.stage("sig/transfer_time", _T_TRANSFER):
            args = tuple(jnp.asarray(p)
                         for p in (hx, hy, sx, sy, pkx, pky, valid))
        if self._bn.pairing_in_pallas():
            self._m_pallas_rows.inc(n)
        dt.dispatched()  # marshal (incl. transfer staging) closes here
        launch = tracing.stage("sig/launch_time", _T_LAUNCH,
                               ctx=dt.span_ctx)
        with self._compiles.compile_span("bls_aggregate", (bucket,),
                                         fresh) as booking, launch:
            out = self._run("bls_aggregate", (bucket,), self._bls, args,
                            booking)
        res = [bool(b) for b in dt.pull(out)[:n]]
        dt.done()
        dt.record_span("jax/bls_aggregate_dispatch", rows=n, bucket=bucket,
                       compile="miss" if fresh else "hit")
        return res

    def bls_verify_committees(self, messages, sig_rows, pk_rows,
                              pk_row_keys=None):
        return self._committee_submit(messages, sig_rows, pk_rows,
                                      pk_row_keys).result()

    def bls_verify_committees_async(self, messages, sig_rows, pk_rows,
                                    pk_row_keys=None):
        """Stage + launch the dispatch NOW; the device executes while
        the caller marshals the next period. `result()` is the host
        pull."""
        return self._committee_submit(messages, sig_rows, pk_rows,
                                      pk_row_keys)

    def das_verify_samples(self, chunks, indices, proofs, roots):
        """One batched keccak dispatch for the whole sample batch: BMT
        recompute of every chunk (128 leaf lanes + 7 pair levels) +
        path fold, `vmap`-shaped over samples × shards. Verdicts are
        bit-identical to the scalar reference because every malformed-
        row rejection is folded into the `valid` plane at marshal time
        (das/proofs.marshal_samples)."""
        from gethsharding_tpu.das import proofs as das_proofs

        jnp = self._jnp
        n = len(chunks)
        if n == 0:
            self.last_wire = None
            return []
        dt = DeviceTimer("das_verify")
        bucket = self._bucket(n)
        fresh = self._note_shape("das_verify", bucket)
        # the committee path's stages (below), so that one set of timers
        # and per-layer metrics reads every op: host marshal and transfer
        # inside the marshal phase, the launch inside the device phase
        with tracing.stage("sig/host_marshal_time", _T_HOST_MARSHAL):
            st = das_proofs.marshal_samples(chunks, indices, proofs, roots,
                                            bucket)
        planes = (st["chunks"], st["sibs"], st["bits"], st["levels"],
                  st["roots"], st["valid"])
        # the staging and the enqueue of the copies, not their
        # completion: the launch below waits for no transfer
        with tracing.stage("sig/transfer_time", _T_TRANSFER):
            args = tuple(jnp.asarray(p) for p in planes)
        sample_bytes = sum(int(p.nbytes) for p in planes)
        # the per-dispatch wire ledger (same contract as the committee
        # path: pure nbytes arithmetic, no device sync) — the sample
        # planes ARE this dispatch's host->device bytes
        self.last_wire = {"op": "das_verify_samples",
                          "wire_bytes": sample_bytes,
                          "sample_wire_bytes": sample_bytes,
                          "rows": n, "bucket": bucket, "wire": self._wire}
        RECORDER.record_wire("das_verify_samples", self.last_wire)
        self._m_wire_bytes.inc(sample_bytes)
        # the chunk plane's part of it: bucket x 4,096, padding included
        self._m_das_chunk_bytes.inc(int(st["chunks"].nbytes))
        tracing.tag_current_add(wire_bytes=sample_bytes,
                                sample_wire_bytes=sample_bytes)
        dt.dispatched()  # marshal (incl. transfer staging) closes here
        launch = tracing.stage("sig/launch_time", _T_LAUNCH,
                               ctx=dt.span_ctx)
        with self._compiles.compile_span("das_verify", (bucket,),
                                         fresh) as booking, launch:
            out = self._run("das_verify", (bucket,),
                            das_proofs.batch_verifier(), args, booking)
        res = [bool(b) for b in dt.pull(out)[:n]]
        dt.done()
        dt.record_span("jax/das_verify_dispatch", rows=n, bucket=bucket,
                       compile="miss" if fresh else "hit",
                       wire_bytes=sample_bytes,
                       sample_wire_bytes=sample_bytes)
        return res

    def das_verify_multiproofs(self, commitments, index_rows, eval_rows,
                               proofs, ns):
        """One dispatch for the whole multiproof batch, both MSMs
        included: the host checks shapes, decodes C and π and writes
        each row's interpolation and vanishing coefficients as digit
        planes (das/poly_proofs.marshal_multiproofs); the device sums
        [r(τ)]₁ and [z_S(τ)]₂ from the SRS's resident fixed-base tables
        (`_srs_tables`), folds A = C − [r(τ)]₁ and checks
        e(A, G2_GEN)·e(−π, Z) == 1 with the committee kernel's
        projective pairing (ops/bn256_jax.das_poly_verify_batch).
        Verdicts are bit-identical to the scalar PCS reference: every
        malformed row is `valid=False` at marshal time, and the rows
        with a point at infinity are decided on the device by the scalar
        pairing's rule.

        On a mesh layout the row planes ship pre-sharded along the
        leading (row) axis, the tables replicated, and the SAME program
        partitions over them — per-row work, so ZERO collectives;
        `last_mesh` records the sharded execution for the non-vacuity
        checks."""
        from gethsharding_tpu.das import poly_proofs

        jnp = self._jnp
        lay = self._layout
        n = len(commitments)
        if n == 0:
            self.last_wire = None
            return []
        dt = DeviceTimer("das_poly_verify")
        # mesh buckets round up to a device multiple so the
        # NamedSharding split is even; padded rows are marshalled
        # rejections exactly like single-device padding
        bucket = lay.mesh_bucket(n) if lay.is_mesh else self._bucket(n)
        tables = self._srs_tables()
        with tracing.stage("sig/host_marshal_time", _T_HOST_MARSHAL):
            st = poly_proofs.marshal_multiproofs(
                commitments, index_rows, eval_rows, proofs, ns, bucket)
        terms = st["terms"]
        shape = ((bucket, terms, lay.n_devices) if lay.is_mesh
                 else (bucket, terms))
        fresh = self._note_shape("das_poly_verify", *shape)
        planes = (st["cx"], st["cy"], st["c_inf"], st["px"], st["py"],
                  st["p_inf"], st["r_digits"], st["z_digits"], st["valid"])
        ship = lay.place if lay.is_mesh else jnp.asarray
        with tracing.stage("sig/transfer_time", _T_TRANSFER):
            args = tuple(ship(p) for p in planes) + tables
        proof_bytes = sum(int(p.nbytes) for p in planes)
        # same wire-ledger contract as the sample path: the row planes
        # ARE this dispatch's host->device bytes (the tables went once)
        self.last_wire = {"op": "das_verify_multiproofs",
                          "wire_bytes": proof_bytes,
                          "sample_wire_bytes": proof_bytes,
                          "rows": n, "bucket": bucket, "wire": self._wire}
        RECORDER.record_wire("das_verify_multiproofs", self.last_wire)
        self._m_wire_bytes.inc(proof_bytes)
        self._m_msm_rows.inc(st["msm_rows"])
        if not lay.is_mesh and self._bn.pairing_in_pallas():
            self._m_pallas_rows.inc(n)
        tracing.tag_current_add(wire_bytes=proof_bytes,
                                sample_wire_bytes=proof_bytes)
        dt.dispatched()  # marshal (incl. transfer staging) closes here
        launch = tracing.stage("sig/launch_time", _T_LAUNCH,
                               ctx=dt.span_ctx)
        with self._compiles.compile_span("das_poly_verify", shape,
                                         fresh) as booking, launch:
            out = self._run("das_poly_verify", shape,
                            self._das_poly_mesh if lay.is_mesh
                            else self._das_poly, args, booking)
        if lay.is_mesh:
            self.last_mesh = {
                "op": "das_verify_multiproofs",
                "n_devices": lay.n_devices, "bucket": bucket,
                "collectives": 0,
                "verdict_devices": len(out.sharding.device_set),
                "vote_total": None,
            }
        res = [bool(b) for b in dt.pull(out)[:n]]
        dt.done()
        # the points both MSMs sum, padded rows and terms included
        windows = st["r_digits"].shape[-1]
        dt.record_span("jax/das_poly_verify_dispatch", rows=n,
                       bucket=bucket, compile="miss" if fresh else "hit",
                       sample_wire_bytes=proof_bytes,
                       msm_terms=bucket * (2 * terms + 1) * windows,
                       device_msm_rows=st["msm_rows"])
        return res

    # (SRS key, (g1 table, g2 table)) once the first multiproof dispatch
    # built them; None before, and on partially-built test instances
    _srs_tabs = None

    def _srs_tables(self) -> tuple:
        """The fixed-base tables of the dev SRS (`pcs.dev_srs()`), built
        ON THE DEVICE by one program the first time a multiproof batch
        comes and held as arguments, never baked into an executable:
        G1 over the first MAX_MULTIPROOF_INDICES powers, G2 over all
        MAX_MULTIPROOF_INDICES + 1, so every set size up to the cap
        reads the same tables. Rebuilt only when the SRS changes (its
        seed or size). On a mesh, replicated to every device."""
        from gethsharding_tpu.das import pcs

        srs = pcs.dev_srs()
        key = (srs.seed, len(srs.g1_powers))
        with self._srs_lock:
            held = self._srs_tabs
            if held is not None and held[0] == key:
                return held[1]
            jnp, bn = self._jnp, self._bn
            k1 = min(pcs.MAX_MULTIPROOF_INDICES, len(srs.g1_powers))
            g1x, g1y, _ = bn.g1_to_limbs(srs.g1_powers[:k1])
            g2x, g2y, _ = bn.g2_to_limbs(srs.g2_powers)
            args = tuple(jnp.asarray(p) for p in (g1x, g1y, g2x, g2y))
            shape = (k1, len(srs.g2_powers), bn.MSM_WINDOW)
            fresh = self._note_shape("das_poly_tables", *shape)
            with self._compiles.compile_span("das_poly_tables", shape,
                                             fresh) as booking:
                tables = self._run("das_poly_tables", shape,
                                   self._das_poly_tables, args, booking)
            if self._layout.is_mesh:
                from jax.sharding import NamedSharding, PartitionSpec

                every = NamedSharding(self._layout.mesh, PartitionSpec())
                tables = tuple(self._jax.device_put(t, every)
                               for t in tables)
            self._srs_tabs = (key, tables)
            return tables

    # -- the staged committee path -----------------------------------------
    # marshal (host limbs + cache resolution) -> transfer (host->device)
    # -> launch (device, async) -> block, pull (result()). Explicit
    # stages so the async form overlaps host staging of batch N+1 with
    # batch N's device execution, each one a `tracing.stage`: a registry
    # timer always (sig/host_marshal_time, sig/transfer_time,
    # sig/launch_time here, sig/block_time and sig/pull_time in
    # `DeviceTimer.pull`), a span and a profiler annotation besides.

    def _committee_submit(self, messages, sig_rows, pk_rows,
                          pk_row_keys) -> VerdictFuture:
        if self._layout.is_mesh:
            return self._committee_submit_mesh(messages, sig_rows,
                                               pk_rows, pk_row_keys)
        dt = DeviceTimer("bls_committee")
        n = len(messages)
        if n == 0:
            self.last_wire = None
            future = VerdictFuture(lambda: [])
            future.result()
            return future
        with tracing.stage("sig/host_marshal_time", _T_HOST_MARSHAL):
            st = self._committee_marshal(messages, sig_rows, pk_rows,
                                         pk_row_keys)
        # the staging and the enqueue of the copies, not their
        # completion: the launch below waits for no transfer. A line
        # table miss adds two stages of its own inside it (cache.py):
        # sig/line_precompute_time and sig/line_stack_time
        with tracing.stage("sig/transfer_time", _T_TRANSFER):
            args, wire = self._committee_transfer(st)
        # the per-dispatch wire ledger is always on (pure nbytes
        # arithmetic, no device sync)
        self.last_wire = wire
        RECORDER.record_wire("bls_verify_committees", wire)
        self._m_wire_bytes.inc(wire["wire_bytes"])
        self._m_g2_bytes.inc(wire["g2_wire_bytes"])
        self._m_pk_hit_bytes.inc(wire["pk_hit_bytes"])
        if self._bn.pairing_in_pallas():
            self._m_pallas_rows.inc(n)
        # stamp the enclosing caller span (the notary's notary/audit);
        # SUMMED, so a multi-dispatch span reports total bytes
        tracing.tag_current_add(wire_bytes=wire["wire_bytes"],
                                pk_hit_bytes=wire["pk_hit_bytes"])
        dt.dispatched()  # marshal (incl. transfer staging) closes here
        launch = tracing.stage("sig/launch_time", _T_LAUNCH,
                               ctx=dt.span_ctx)
        if st["precomp"]:
            # the table-fed Miller and the final exponentiation, one
            # program over the whole bucket like the recompute kernel
            op, fn = "bls_committee_precomp", self._precomp_full
            args += (self._gen_lines_dev,)
        else:
            op, fn = "bls_committee", (
                self._bls_committee_u16 if self._wire_u16
                else self._bls_committee)
        shape = (st["bucket"], st["width"], self._wire)
        with self._compiles.compile_span(op, shape,
                                         st["fresh"]) as booking, launch:
            # async dispatch: returns pre-execution
            out = self._run(op, shape, fn, args, booking)
        # finalize must close over SCALARS, not the marshal dict: `st`
        # pins every host limb plane (MBs per dispatch) until result(),
        # and an overlapped K-period pipeline holds K of them at once
        bucket, width, fresh = st["bucket"], st["width"], st["fresh"]
        # rows served from resident line tables, and rows precomputed
        line_hit = wire["pk_hit_rows"] if st["precomp"] else 0
        line_miss = (wire["pk_rows"] - line_hit) if st["precomp"] else 0

        def finalize():
            # the checked pull is the barrier: block-vs-pull divergence
            # lands on perfwatch/timer_suspect
            res = [bool(b) for b in dt.pull(out)[:n]]
            dt.done()
            # the checked pull above means the span closes only after
            # the dispatch actually executed; on the async path it
            # additionally covers the overlapped wait
            dt.record_span(
                "jax/bls_committee_dispatch", rows=n, bucket=bucket,
                width=width, wire=self._wire,
                compile="miss" if fresh else "hit",
                wire_bytes=wire["wire_bytes"],
                pk_hit_bytes=wire["pk_hit_bytes"],
                line_miss_rows=line_miss, line_hit_rows=line_hit)
            return res

        return VerdictFuture(finalize)

    def _committee_submit_mesh(self, messages, sig_rows, pk_rows,
                               pk_row_keys) -> VerdictFuture:
        """The mesh committee audit: the same marshal -> transfer ->
        dispatch staging, but every plane ships pre-split along the
        shard axis (each device receives ONLY its slab's bytes, resident
        pk rows come from ITS cache shard) and the launch is ONE pjit'd
        `shard_map` step whose vote-total `psum` is the only
        cross-device traffic — counted per compiled executable from the
        AOT HLO into `last_mesh["collectives"]`. Verdicts are
        bit-identical to the single-device path: same kernels, same
        padding semantics, only placement differs."""
        import numpy as np

        dt = DeviceTimer("bls_committee_mesh")
        lay = self._layout
        n = len(messages)
        if n == 0:
            self.last_wire = None
            self.last_mesh = None
            future = VerdictFuture(lambda: [])
            future.result()
            return future
        # the mesh twin's stages: the pk planes of a resident or precomp
        # row are placed as they are resolved, inside the marshal stage
        with tracing.stage("sig/host_marshal_time", _T_HOST_MARSHAL):
            bucket = lay.mesh_bucket(n)
            pad = bucket - n
            width = marshal.committee_width(sig_rows, pk_rows)
            rows = list(pk_rows) + [[]] * pad
            keys = marshal.normalize_row_keys(pk_row_keys, len(rows))
            resident = self._resident and keys is not None
            precomp = self._precomp and resident
            # the compile-cache key includes the device count: re-laying the
            # same process over a different mesh is a fresh XLA program (and
            # the precomp step is its own program again)
            fresh = self._note_shape(
                "bls_committee_mesh_precomp" if precomp
                else "bls_committee_mesh",
                bucket, width, self._wire, lay.n_devices)
            check = os.environ.get("GETHSHARDING_CHECK") == "1"
            host = marshal.committee_host_planes(
                self._bn, messages, sig_rows, pad, width,
                marshal.wire_dtype(self._wire_u16, check))
            st = {"n": n, "bucket": bucket, "pad": pad, "width": width,
                  "fresh": fresh, "check": check,
                  "pk_rows": sum(1 for r in rows if r),
                  "hit_rows": 0, "hit_bytes": 0}
            conv = marshal.wire_converter(self._wire_u16, check)
            hx, hy = conv(host["hx"]), conv(host["hy"])
            sx, sy = conv(host["sx"]), conv(host["sy"])
            sm, hok = host["sm"], host["hok"]
            wire_bytes = (hx.nbytes + hy.nbytes + sx.nbytes + sy.nbytes
                          + sm.nbytes + hok.nbytes)
            if precomp:
                tab, inf, g2_bytes = self._mesh_line_tables(st, rows, keys,
                                                            lay)
            elif resident:
                px, py, pm, g2_bytes = self._mesh_pk_planes(st, rows, keys,
                                                            lay)
            else:
                pxh, pyh, pmh = self._pk_rows_to_limbs(rows, width,
                                                       row_keys=keys)
                pxh, pyh = conv(pxh), conv(pyh)
                g2_bytes = pxh.nbytes + pyh.nbytes + pmh.nbytes
                px, py, pm = lay.place(pxh), lay.place(pyh), lay.place(pmh)
            wire_bytes += g2_bytes
        with tracing.stage("sig/transfer_time", _T_TRANSFER):
            if precomp:
                args = (lay.place(hx), lay.place(hy), lay.place(sx),
                        lay.place(sy), lay.place(sm), tab, inf,
                        lay.place(hok), self._gen_lines_mesh)
            else:
                args = (lay.place(hx), lay.place(hy), lay.place(sx),
                        lay.place(sy), lay.place(sm), px, py, pm,
                        lay.place(hok))
        wire = {"wire_bytes": int(wire_bytes),
                "g2_wire_bytes": int(g2_bytes),
                "pk_hit_bytes": int(st["hit_bytes"]),
                "pk_rows": int(st["pk_rows"]),
                "pk_hit_rows": int(st["hit_rows"]),
                "resident": resident, "precomp": precomp,
                "wire": self._wire}
        self.last_wire = wire
        RECORDER.record_wire("bls_verify_committees", wire)
        self._m_wire_bytes.inc(wire["wire_bytes"])
        self._m_g2_bytes.inc(wire["g2_wire_bytes"])
        self._m_pk_hit_bytes.inc(wire["pk_hit_bytes"])
        tracing.tag_current_add(wire_bytes=wire["wire_bytes"],
                                pk_hit_bytes=wire["pk_hit_bytes"])
        exe_key = (bucket, width, self._wire,
                   "precomp" if precomp else "recompute")
        mesh_fn = (self._bls_committee_mesh_precomp if precomp
                   else self._bls_committee_mesh)
        dt.dispatched()
        with self._compiles.compile_span(
                "bls_committee_mesh_precomp" if precomp
                else "bls_committee_mesh",
                (bucket, width, self._wire, lay.n_devices), fresh), \
                tracing.stage("sig/launch_time", _T_LAUNCH,
                              ctx=dt.span_ctx):
            exe = self._mesh_exec.get(exe_key)
            if exe is None:
                # AOT: one .lower().compile() gives the executable AND
                # its optimized HLO, so the one-collective assertion is
                # a free byproduct of the compile we had to do anyway
                exe = mesh_fn.lower(*args).compile()
                self._mesh_exec[exe_key] = exe
                self._mesh_collectives[exe_key] = \
                    layout_mod.count_collectives(exe.as_text())
                self._g_mesh_collectives.set(
                    self._mesh_collectives[exe_key])
            out, votes = exe(*args)
        collectives = self._mesh_collectives[exe_key]
        mesh_rec = {"op": "bls_verify_committees",
                    "n_devices": lay.n_devices, "bucket": bucket,
                    "width": width, "collectives": collectives,
                    "precomp": precomp,
                    "verdict_devices": None, "vote_total": None}
        self.last_mesh = mesh_rec

        def finalize():
            res = [bool(b) for b in dt.pull(out)[:n]]
            # non-vacuity evidence for the tests/bench: the verdict
            # plane really was sharded over the mesh, and the psum'd
            # vote total agrees with the verdict plane it reduced
            mesh_rec["verdict_devices"] = len(out.sharding.device_set)
            mesh_rec["vote_total"] = int(np.asarray(votes))
            dt.done()
            dt.record_span(
                "jax/bls_committee_mesh_dispatch", rows=n, bucket=bucket,
                width=width, wire=self._wire, n_devices=lay.n_devices,
                collectives=collectives,
                compile="miss" if fresh else "hit",
                wire_bytes=wire["wire_bytes"],
                pk_hit_bytes=wire["pk_hit_bytes"])
            return res

        return VerdictFuture(finalize)

    def _committee_marshal(self, messages, sig_rows, pk_rows,
                           pk_row_keys) -> dict:
        """Stage 1, host only: padding policy, limb marshalling of the
        fresh-per-period buffers (hashes, signatures, masks), pk-row
        cache resolution (device hits claimed, misses marshalled)."""
        n = len(messages)
        bucket = self._bucket(n)
        pad = bucket - n
        width = marshal.committee_width(sig_rows, pk_rows)
        rows = list(pk_rows) + [[]] * pad
        keys = marshal.normalize_row_keys(pk_row_keys, len(rows))
        resident = self._resident and keys is not None
        # the precomp path needs the resident LRU (line tables are its
        # residents) — keyless or resident-off dispatches fall back to
        # the recompute kernel, today's path bit-for-bit
        precomp = self._precomp and resident
        # the compile-cache key INCLUDES the wire dtype: the u16 wire
        # compiles a different XLA program for the same (bucket, width),
        # so counting it against the other wire's entry would book a
        # real recompile as a hit. The precomp path is its own op (line
        # tables in, no G2 planes).
        fresh = self._note_shape(
            "bls_committee_precomp" if precomp else "bls_committee",
            bucket, width, self._wire)
        check = os.environ.get("GETHSHARDING_CHECK") == "1"
        host = marshal.committee_host_planes(
            self._bn, messages, sig_rows, pad, width,
            marshal.wire_dtype(self._wire_u16, check))
        st = {"n": n, "bucket": bucket, "pad": pad, "width": width,
              "fresh": fresh, "check": check,
              "pk_rows": sum(1 for r in rows if r),
              "hx": host["hx"], "hy": host["hy"], "hok": host["hok"],
              "sx": host["sx"], "sy": host["sy"], "sm": host["sm"],
              "resident": resident, "precomp": precomp}
        if precomp:
            self._line_resolve(st, rows, keys)
        elif resident:
            self._pk_resident_resolve(st, rows, keys)
        else:
            px, py, pm = self._pk_rows_to_limbs(rows, width, row_keys=keys)
            st["px"], st["py"], st["pm"] = px, py, pm
        return st

    def _committee_transfer(self, st) -> tuple:
        """Stage 2, host->device: ship the fresh-per-period buffers (+
        any pk-row misses) and assemble the kernel args. Returns
        (args, wire_ledger); ledger bytes are LOGICAL wire bytes — what
        crosses the host->device link for this dispatch. Device-cache
        hits and on-device stacking contribute zero."""
        jnp = self._jnp
        conv = marshal.wire_converter(self._wire_u16, st["check"])
        hx, hy = conv(st["hx"]), conv(st["hy"])
        sx, sy = conv(st["sx"]), conv(st["sy"])
        sm, hok = st["sm"], st["hok"]
        wire_bytes = (hx.nbytes + hy.nbytes + sx.nbytes + sy.nbytes
                      + sm.nbytes + hok.nbytes)
        if st["precomp"]:
            # line tables replace the pk planes entirely: warm rows
            # ship NOTHING (g2_bytes counts only cold precompute input)
            tab, inf, g2_bytes = self._line_tables(st)
            hit_bytes, hit_rows = st["hit_bytes"], st["hit_rows"]
            args = (jnp.asarray(hx), jnp.asarray(hy), jnp.asarray(sx),
                    jnp.asarray(sy), jnp.asarray(sm), tab, inf,
                    jnp.asarray(hok))
        else:
            if st["resident"]:
                px, py, pm, g2_bytes = self._pk_resident_planes(st)
                hit_bytes, hit_rows = st["hit_bytes"], st["hit_rows"]
            else:
                pxh, pyh, pmh = conv(st["px"]), conv(st["py"]), st["pm"]
                g2_bytes = pxh.nbytes + pyh.nbytes + pmh.nbytes
                px, py, pm = (jnp.asarray(pxh), jnp.asarray(pyh),
                              jnp.asarray(pmh))
                hit_bytes = hit_rows = 0
            args = (jnp.asarray(hx), jnp.asarray(hy), jnp.asarray(sx),
                    jnp.asarray(sy), jnp.asarray(sm), px, py, pm,
                    jnp.asarray(hok))
        wire_bytes += g2_bytes
        wire = {"wire_bytes": int(wire_bytes),
                "g2_wire_bytes": int(g2_bytes),
                "pk_hit_bytes": int(hit_bytes),
                "pk_rows": int(st["pk_rows"]),
                "pk_hit_rows": int(hit_rows),
                "resident": st["resident"],
                "precomp": st["precomp"],
                "wire": self._wire}
        return args, wire

    # populated by EVERY committee dispatch (no sync, pure nbytes
    # arithmetic): {wire_bytes, g2_wire_bytes, pk_hit_bytes, pk_rows,
    # pk_hit_rows, resident, precomp, wire} — the transfer-attribution
    # ledger the residency/precomp tests assert on (steady state: g2_wire_bytes == 0; precomp True
    # when the dispatch consumed resident line tables)
    last_wire: dict | None = None

    # populated by every MESH dispatch: {op, n_devices, bucket, width,
    # collectives, verdict_devices, vote_total} — the non-vacuity
    # evidence (the pjit path really produced sharded arrays; exactly
    # one cross-device collective per committee step). None on
    # single-device layouts.
    last_mesh: dict | None = None
