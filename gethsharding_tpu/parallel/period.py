"""The per-period cross-shard pipeline: verify → tally → approve.

This is the framework's "training step": for every shard in a period,
verify the aggregate BLS committee vote on the shard's collation header
(batched pairing kernel), tally accepted votes, apply the quorum rule, and
all-reduce the period totals — laid out so the shard axis shards over a
`jax.sharding.Mesh` (BASELINE.md configs 3 and 5; SURVEY.md §2.2 row 1:
shard-level data parallelism is the reference's only scaling axis, here it
is the mesh axis and the tallies ride ICI collectives).

Two dispatch modes, same math:
- single-device: one jitted batch over all shards;
- mesh: `shard_map` with each device owning a contiguous shard slab and
  `psum` for the cross-shard reductions.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as PS

from gethsharding_tpu.crypto import bn256 as bls
from gethsharding_tpu.ops import bn256_jax as bn
from gethsharding_tpu.params import Config, DEFAULT_CONFIG
from gethsharding_tpu.parallel.mesh import (
    hierarchical_psum, shard_axis_sharding)


class PeriodInputs(NamedTuple):
    """Device arrays for one period across S shards (leading axis = shard)."""

    hx: jnp.ndarray    # (S, 22) G1 hash-to-curve of each header
    hy: jnp.ndarray
    sx: jnp.ndarray    # (S, 22) aggregate committee signature
    sy: jnp.ndarray
    pkx: jnp.ndarray   # (S, 2, 22) aggregate committee public key
    pky: jnp.ndarray
    vote_count: jnp.ndarray  # (S,) int32 — votes aggregated per shard
    has_header: jnp.ndarray  # (S,) bool — shard has a submission this period


class PeriodOutputs(NamedTuple):
    verified: jnp.ndarray       # (S,) bool — aggregate signature valid
    approved: jnp.ndarray       # (S,) bool — verified & quorum reached
    total_votes: jnp.ndarray    # () int32 — Σ counted votes (all shards)
    total_approved: jnp.ndarray  # () int32 — Σ approved shards


def _tally(ok, counted, quorum: int, mesh: Optional[Mesh]) -> PeriodOutputs:
    """Quorum + period totals, reduced hierarchically over the mesh —
    the ONE tail shared by both pipeline granularities."""
    approved = ok & (counted >= quorum)
    total_votes = jnp.sum(counted)
    total_approved = jnp.sum(approved.astype(jnp.int32))
    if mesh is not None:
        total_votes = hierarchical_psum(total_votes, mesh)
        total_approved = hierarchical_psum(total_approved, mesh)
    return PeriodOutputs(ok, approved, total_votes, total_approved)


def _step(inp: PeriodInputs, quorum: int, mesh: Optional[Mesh]):
    # the platform's pairing kernels on one device; XLA's under
    # shard_map, where a `pallas_call` fails at trace
    ok = bn.bls_verify_aggregate_batch(
        inp.hx, inp.hy, inp.sx, inp.sy, inp.pkx, inp.pky, inp.has_header,
        pallas=None if mesh is None else False)
    return _tally(ok, jnp.where(ok, inp.vote_count, 0), quorum, mesh)


def _compile_step(step, quorum: int, mesh: Optional[Mesh], tuple_cls):
    """jit (single device) or shard_map-jit (mesh) of a period step over
    `tuple_cls` inputs; the leading shard axis splits over ALL mesh axes
    (1-D shard meshes and 2-D ("dcn", "ici") multi-host meshes alike),
    with tallies reduced hierarchically — ICI first, then DCN."""
    if mesh is None:
        return jax.jit(lambda inp: step(inp, quorum, None))
    n_fields = len(tuple_cls._fields)
    spec = PS(tuple(mesh.axis_names))
    return jax.jit(shard_map(
        lambda inp: step(inp, quorum, mesh),
        mesh=mesh,
        in_specs=(tuple_cls(*([spec] * n_fields)),),
        out_specs=PeriodOutputs(spec, spec, PS(), PS()),
    ))


def _run_padded(fn, mesh: Optional[Mesh], inputs, tuple_cls):
    """Run a compiled period step, padding the shard axis with masked
    zero rows (has_header False) to the next multiple of the mesh size
    and slicing the per-shard outputs back — masked rows contribute
    nothing to the psum tallies."""
    n = int(inputs[0].shape[0])
    if mesh is None:
        return fn(inputs)
    n_dev = mesh.devices.size
    padded = -(-n // n_dev) * n_dev
    if padded != n:
        pad = padded - n

        def pad_rows(a):
            widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
            return jnp.pad(a, widths)

        inputs = tuple_cls(*(pad_rows(a) for a in inputs))
    sharding = shard_axis_sharding(mesh)
    inputs = tuple_cls(*(jax.device_put(a, sharding) for a in inputs))
    out = fn(inputs)
    if padded != n:
        out = PeriodOutputs(
            verified=out.verified[:n], approved=out.approved[:n],
            total_votes=out.total_votes,
            total_approved=out.total_approved)
    return out


class PeriodPipeline:
    """Compiled per-period verifier over PRE-AGGREGATED committee points,
    optionally sharded over a mesh; uneven shard counts pad with masked
    rows (see `_run_padded`)."""

    def __init__(self, config: Config = DEFAULT_CONFIG,
                 mesh: Optional[Mesh] = None):
        self.config = config
        self.mesh = mesh
        self._fn = _compile_step(_step, config.quorum_size, mesh,
                                 PeriodInputs)

    def run(self, inputs: PeriodInputs) -> PeriodOutputs:
        return _run_padded(self._fn, self.mesh, inputs, PeriodInputs)

    # -- host-side assembly -------------------------------------------------

    def build_inputs(self, headers: Sequence[Optional[bytes]],
                     agg_sigs: Sequence[Optional[bls.G1Point]],
                     agg_pks: Sequence[Optional[bls.G2Point]],
                     vote_counts: Sequence[int]) -> PeriodInputs:
        """Host records -> device arrays. `headers[i] is None` marks a
        shard with no submission this period (row masked out)."""
        hashes = [bls.hash_to_g1(h) if h is not None else None
                  for h in headers]
        hx, hy, hok = bn.g1_to_limbs(hashes)
        sx, sy, sok = bn.g1_to_limbs(list(agg_sigs))
        pkx, pky, pok = bn.g2_to_limbs(list(agg_pks))
        has_header = hok & sok & pok
        return PeriodInputs(
            hx=jnp.asarray(hx), hy=jnp.asarray(hy),
            sx=jnp.asarray(sx), sy=jnp.asarray(sy),
            pkx=jnp.asarray(pkx), pky=jnp.asarray(pky),
            vote_count=jnp.asarray(np.asarray(vote_counts, np.int32)),
            has_header=jnp.asarray(has_header),
        )


class CommitteePeriodInputs(NamedTuple):
    """Per-period inputs at COMMITTEE granularity (leading axis = shard):
    raw vote signatures and voter pubkeys, aggregated on device inside
    the verification dispatch (the production audit path)."""

    hx: jnp.ndarray        # (S, 22) G1 hash-to-curve of each header
    hy: jnp.ndarray
    sigx: jnp.ndarray      # (S, C, 22) per-vote signatures
    sigy: jnp.ndarray
    sig_mask: jnp.ndarray  # (S, C) bool — filled vote slots
    pkx: jnp.ndarray       # (S, C, 2, 22) voter pubkeys
    pky: jnp.ndarray
    pk_mask: jnp.ndarray   # (S, C) bool
    has_header: jnp.ndarray  # (S,) bool


def _committee_step(inp: CommitteePeriodInputs, quorum: int,
                    mesh: Optional[Mesh]):
    ok = bn.bls_aggregate_verify_committee_batch(
        inp.hx, inp.hy, inp.sigx, inp.sigy, inp.sig_mask,
        inp.pkx, inp.pky, inp.pk_mask, inp.has_header,
        pallas=None if mesh is None else False)   # as in `_step`
    # the vote count IS the filled signature slots — the device holds the
    # ground truth, so a stale/forged host-side count cannot inflate the
    # quorum
    counted = jnp.where(ok, jnp.sum(inp.sig_mask.astype(jnp.int32),
                                    axis=-1), 0)
    return _tally(ok, counted, quorum, mesh)


class CommitteePeriodPipeline:
    """The production period step: per-shard committee aggregation (masked
    projective tree reduction over the committee axis) + batched pairing
    verification + quorum tally, with the SHARD axis over the mesh and
    tallies riding `psum` — aggregation work stays device-local, only the
    two scalar totals cross the interconnect."""

    def __init__(self, config: Config = DEFAULT_CONFIG,
                 mesh: Optional[Mesh] = None):
        self.config = config
        self.mesh = mesh
        self._fn = _compile_step(_committee_step, config.quorum_size, mesh,
                                 CommitteePeriodInputs)

    def run(self, inputs: CommitteePeriodInputs) -> PeriodOutputs:
        return _run_padded(self._fn, self.mesh, inputs,
                           CommitteePeriodInputs)

    def build_inputs(self, headers: Sequence[Optional[bytes]],
                     sig_rows: Sequence[Sequence[bls.G1Point]],
                     pk_rows: Sequence[Sequence[bls.G2Point]],
                     width: Optional[int] = None) -> CommitteePeriodInputs:
        """Host vote records -> committee-granular device arrays. The
        committee axis pads to `width` (default: the config committee
        size) so the compiled shape is period-invariant."""
        width = (width if width is not None
                 else self.config.committee_size)
        hashes = [bls.hash_to_g1(h) if h is not None else None
                  for h in headers]
        hx, hy, hok = bn.g1_to_limbs(hashes)
        sigx, sigy, sig_mask = bn.g1_committee_to_limbs(sig_rows, width)
        pkx, pky, pk_mask = bn.g2_committee_to_limbs(pk_rows, width)
        return CommitteePeriodInputs(
            hx=jnp.asarray(hx), hy=jnp.asarray(hy),
            sigx=jnp.asarray(sigx), sigy=jnp.asarray(sigy),
            sig_mask=jnp.asarray(sig_mask),
            pkx=jnp.asarray(pkx), pky=jnp.asarray(pky),
            pk_mask=jnp.asarray(pk_mask),
            has_header=jnp.asarray(hok),
        )
