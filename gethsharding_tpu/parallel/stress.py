"""The multi-chip stress pipeline: BASELINE.md config 5.

One fused period step over S shards sharded across the ``"shard"`` mesh
axis, combining every per-period kernel the framework has:

  addHeader vote-plane reset  (ops/smc_jax.add_header_reset_masked)
  -> submitVote batch          (ops/smc_jax.submit_votes_batch:
                                committee sampling, bitfield, quorum)
  -> committee BLS aggregation + verification (ops/bn256_jax: masked
                                 projective tree sum + one Miller
                                 product per shard — the production
                                 audit dispatch)
  -> collation tx replay        (ops/replay_jax: batched ecrecover +
                                 ordered state transitions + state roots)
  -> period totals as `psum` over ICI (the all-reduce of the north star)

Each device owns a contiguous slab of shards with DISTINCT data; uneven
shard counts pad with masked rows (has_header=False, invalid attempts) —
`run` handles the padding transparently, like PeriodPipeline.

Every sub-kernel is differential-tested on its own elsewhere; the test
for this module checks mesh-vs-single-device bit identity, which is the
property the stress config exists to demonstrate.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as PS

from gethsharding_tpu.ops import bn256_jax as bn
from gethsharding_tpu.ops import replay_jax, secp256k1_jax, smc_jax
from gethsharding_tpu.params import Config, DEFAULT_CONFIG
from gethsharding_tpu.parallel.mesh import shard_axis_sharding


class StressInputs(NamedTuple):
    """Leading axis S = shards on every field except the replicated tail."""

    # SMC vote plane
    has_voted: jnp.ndarray       # (S, C) bool
    vote_count: jnp.ndarray      # (S,) int32
    last_submitted: jnp.ndarray  # (S,) int32
    last_approved: jnp.ndarray   # (S,) int32
    is_elected: jnp.ndarray      # (S,) bool
    chunk_root: jnp.ndarray      # (S, 32) uint8 — prior record roots
    # this period's headers
    new_header: jnp.ndarray      # (S,) bool
    new_chunk_root: jnp.ndarray  # (S, 32) uint8
    # vote attempts, V rows per shard (padded with valid=False)
    att_index: jnp.ndarray       # (S, V) int32
    att_pool_index: jnp.ndarray  # (S, V) int32
    att_sender: jnp.ndarray      # (S, V, 20) uint8
    att_chunk_root: jnp.ndarray  # (S, V, 32) uint8
    att_deposited: jnp.ndarray   # (S, V) bool
    att_valid: jnp.ndarray       # (S, V) bool
    # committee BLS votes per shard (aggregated ON DEVICE)
    hx: jnp.ndarray              # (S, NLIMBS)
    hy: jnp.ndarray
    sigx: jnp.ndarray            # (S, Cw, NLIMBS) raw vote signatures
    sigy: jnp.ndarray
    sig_mask: jnp.ndarray        # (S, Cw) bool
    pkx: jnp.ndarray             # (S, Cw, 2, NLIMBS) voter pubkeys
    pky: jnp.ndarray
    pk_mask: jnp.ndarray         # (S, Cw) bool
    agg_valid: jnp.ndarray       # (S,) bool
    # collation replay (see ops/replay_jax.ReplayInputs)
    addrs: jnp.ndarray
    nonces: jnp.ndarray
    balances: jnp.ndarray
    coinbase_ix: jnp.ndarray
    tx_e: jnp.ndarray
    tx_r: jnp.ndarray
    tx_s: jnp.ndarray
    tx_recid: jnp.ndarray
    tx_nonce: jnp.ndarray
    tx_gas_limit: jnp.ndarray
    tx_intrinsic: jnp.ndarray
    tx_price: jnp.ndarray
    tx_value: jnp.ndarray
    tx_to: jnp.ndarray
    tx_valid: jnp.ndarray


class StressOutputs(NamedTuple):
    accepted: jnp.ndarray        # (S, V) bool — accepted vote attempts
    vote_count: jnp.ndarray      # (S,) int32
    is_elected: jnp.ndarray      # (S,) bool
    agg_ok: jnp.ndarray          # (S,) bool — aggregate signature valid
    tx_status: jnp.ndarray       # (S, T) bool
    roots: jnp.ndarray           # (S, 32) uint8 — post-replay state roots
    total_votes: jnp.ndarray     # () int32  — psum over the mesh
    total_elected: jnp.ndarray   # () int32
    total_txs: jnp.ndarray       # () int32


def _step(inp: StressInputs, pool_addr, blockhash, period, sample_size,
          committee_size: int, quorum_size: int, axis,
          axis_sizes: tuple = ()):
    """`axis`: None (single device) or the mesh axis-name tuple. With a
    multi-axis mesh (("dcn", "ici")) the slab index linearizes over the
    axes in order and the tallies psum over all of them — ICI innermost
    (hierarchical_psum ordering)."""
    s_local, v = inp.att_index.shape
    t = inp.tx_recid.shape[1]

    # 1. addHeader resets
    state = smc_jax.VoteState(
        has_voted=inp.has_voted, vote_count=inp.vote_count,
        last_submitted=inp.last_submitted, last_approved=inp.last_approved,
        is_elected=inp.is_elected, chunk_root=inp.chunk_root)
    state = smc_jax.add_header_reset_masked(
        state, inp.new_header, period, inp.new_chunk_root)

    # 2. submitVote batch — attempts flattened to LOCAL slab indices for
    # state routing, with GLOBAL shard ids for the committee sampling
    flat = lambda x: x.reshape((s_local * v,) + x.shape[2:])
    shard_ids = jnp.repeat(jnp.arange(s_local, dtype=jnp.int32), v)
    if axis is not None:
        device_ix = jnp.int32(0)
        for name, size in zip(axis, axis_sizes):
            device_ix = (device_ix * size
                         + jax.lax.axis_index(name).astype(jnp.int32))
        base = device_ix * s_local
    else:
        base = jnp.int32(0)
    attempts = smc_jax.VoteAttempts(
        shard=shard_ids, index=flat(inp.att_index),
        pool_index=flat(inp.att_pool_index), sender=flat(inp.att_sender),
        chunk_root=flat(inp.att_chunk_root),
        deposited=flat(inp.att_deposited), valid=flat(inp.att_valid))
    state, accepted = smc_jax.submit_votes_batch(
        state, pool_addr, attempts, period=period, blockhash=blockhash,
        sample_size=sample_size, committee_size=committee_size,
        quorum_size=quorum_size, sample_shard=shard_ids + base)

    # 3. committee BLS aggregation + verification (masked projective tree
    # sum, then one shared-accumulator Miller product per local shard;
    # XLA's pairing under shard_map, where a `pallas_call` fails at trace)
    agg_ok = bn.bls_aggregate_verify_committee_batch(
        inp.hx, inp.hy, inp.sigx, inp.sigy, inp.sig_mask,
        inp.pkx, inp.pky, inp.pk_mask, inp.agg_valid,
        pallas=None if axis is None else False)

    # 4. collation replay (batched recovery + ordered transitions)
    tflat = lambda x: x.reshape((s_local * t,) + x.shape[2:])
    qx, qy, rec_ok = secp256k1_jax.ecrecover_batch(
        tflat(inp.tx_e), tflat(inp.tx_r), tflat(inp.tx_s),
        tflat(inp.tx_recid), tflat(inp.tx_valid))
    senders = replay_jax.pubkeys_to_addresses(qx, qy).reshape(s_local, t, 20)
    sender_ok = rec_ok.reshape(s_local, t)
    nonces, balances, tx_status, _ = jax.vmap(replay_jax._shard_replay)(
        inp.addrs, inp.nonces, inp.balances, inp.coinbase_ix, senders,
        sender_ok, inp.tx_nonce, inp.tx_gas_limit, inp.tx_intrinsic,
        inp.tx_price, inp.tx_value, inp.tx_to, inp.tx_valid)
    roots = replay_jax._state_root(inp.addrs, nonces, balances)

    # 5. period totals over the mesh
    total_votes = jnp.sum(accepted.astype(jnp.int32))
    total_elected = jnp.sum(state.is_elected.astype(jnp.int32))
    total_txs = jnp.sum(tx_status.astype(jnp.int32))
    if axis is not None:
        for name in reversed(axis):  # ICI first, then DCN (§5.8)
            total_votes = jax.lax.psum(total_votes, axis_name=name)
            total_elected = jax.lax.psum(total_elected, axis_name=name)
            total_txs = jax.lax.psum(total_txs, axis_name=name)

    return StressOutputs(
        accepted=accepted.reshape(s_local, v), vote_count=state.vote_count,
        is_elected=state.is_elected, agg_ok=agg_ok, tx_status=tx_status,
        roots=roots, total_votes=total_votes, total_elected=total_elected,
        total_txs=total_txs)


class StressPipeline:
    """Compiled config-5 step, single-device or mesh-sharded.

    Committee-sampling parity across layouts: the keccak sampling must see
    GLOBAL shard ids while state routing uses LOCAL slab indices under
    shard_map — `_step` derives the global ids from `lax.axis_index`.
    """

    def __init__(self, config: Config = DEFAULT_CONFIG,
                 mesh: Optional[Mesh] = None):
        self.config = config
        self.mesh = mesh
        c, q = config.committee_size, config.quorum_size

        def run_fn(inp, pool_addr, blockhash, period, sample_size, axis,
                   axis_sizes=()):
            return _step(inp, pool_addr, blockhash, period, sample_size,
                         c, q, axis, axis_sizes)

        if mesh is None:
            self._fn = jax.jit(
                lambda inp, pool, bh, per, ss: run_fn(inp, pool, bh, per,
                                                      ss, None))
        else:
            # any mesh rank: 1-D ("shard",) and 2-D ("dcn", "ici") alike —
            # the shard axis splits over ALL mesh axes, tallies reduce
            # hierarchically
            axes = tuple(mesh.axis_names)
            sizes = tuple(mesh.shape[name] for name in axes)
            n_fields = len(StressInputs._fields)
            self._fn = jax.jit(shard_map(
                lambda inp, pool, bh, per, ss: run_fn(
                    inp, pool, bh, per, ss, axes, sizes),
                mesh=mesh,
                in_specs=(StressInputs(*([PS(axes)] * n_fields)),
                          PS(), PS(), PS(), PS()),
                out_specs=StressOutputs(
                    *([PS(axes)] * 6 + [PS()] * 3)),
            ))

    def run(self, inputs: StressInputs, pool_addr, blockhash, period,
            sample_size) -> StressOutputs:
        n = int(inputs.has_voted.shape[0])
        padded = n
        if self.mesh is not None:
            n_dev = self.mesh.devices.size
            padded = -(-n // n_dev) * n_dev
            if padded != n:
                pad = padded - n

                def pad_rows(a):
                    widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
                    return jnp.pad(a, widths)

                inputs = StressInputs(*(pad_rows(a) for a in inputs))
            sharding = shard_axis_sharding(self.mesh)
            inputs = StressInputs(
                *(jax.device_put(a, sharding) for a in inputs))
        out = self._fn(inputs, jnp.asarray(pool_addr),
                       jnp.asarray(blockhash), jnp.int32(period),
                       jnp.int32(sample_size))
        if padded != n:
            out = StressOutputs(
                *(a[:n] for a in out[:6]), *out[6:])
        return out


# == distinct-per-shard workload builder ===================================


def build_stress_inputs(n_shards: int, *, votes_per_shard: int = 3,
                        txs_per_shard: int = 2, committee_size: int = 135,
                        period: int = 1, seed: int = 7):
    """Distinct per-shard data for the stress step (host-side, scalar
    crypto): a notary pool, per-shard sampled vote attempts that the
    committee check will accept, per-shard aggregate BLS votes on the
    shard's own digest, and per-shard signed transfer transactions.

    Returns (inputs, pool_addr, blockhash, sample_size, expected) where
    `expected` carries host-computed acceptance data for assertions."""
    from gethsharding_tpu.core import state_processor as sp
    from gethsharding_tpu.core.types import Transaction
    from gethsharding_tpu.crypto import bn256 as bls
    from gethsharding_tpu.crypto import secp256k1
    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.smc.state_machine import vote_digest
    from gethsharding_tpu.utils.hexbytes import Address20, Hash32

    rng = np.random.default_rng(seed)
    pool_size = committee_size
    pool = [Address20(bytes(rng.integers(1, 255, 20, dtype=np.uint8)))
            for _ in range(pool_size)]
    pool_addr = np.stack([np.frombuffer(bytes(a), np.uint8) for a in pool])
    blockhash = bytes(rng.integers(0, 255, 32, dtype=np.uint8))
    sample_size = pool_size

    def sampled_slot(pool_index: int, shard: int) -> int:
        pre = (blockhash + pool_index.to_bytes(32, "big")
               + shard.to_bytes(32, "big"))
        return int.from_bytes(keccak256(pre), "big") % sample_size

    s = n_shards
    v = votes_per_shard
    t = txs_per_shard
    z = np.zeros
    roots = rng.integers(0, 255, (s, 32), dtype=np.uint8)

    att_index = z((s, v), np.int32)
    att_pool_index = z((s, v), np.int32)
    att_sender = z((s, v, 20), np.uint8)
    att_root = np.repeat(roots[:, None, :], v, axis=1)
    att_deposited = np.ones((s, v), bool)
    att_valid = np.ones((s, v), bool)
    for shard in range(s):
        for j in range(v):
            # attempt j claims pool slot j; its sender must be the member
            # the committee sampling selects for (j, shard)
            att_index[shard, j] = j
            att_pool_index[shard, j] = j
            att_sender[shard, j] = pool_addr[sampled_slot(j, shard)]

    # distinct committee BLS votes per shard, aggregated ON DEVICE (small
    # committee for host build speed; the pairing cost per shard is
    # committee-size-invariant and the tree cost is measured by the
    # committee width knob)
    keys = [bls.bls_keygen(bytes([seed % 256, i])) for i in range(2)]
    h_pts, sig_rows, pk_rows = [], [], []
    for shard in range(s):
        digest = vote_digest(shard, period, Hash32(bytes(roots[shard])))
        h_pts.append(bls.hash_to_g1(digest))
        sig_rows.append([bls.bls_sign(digest, sk) for sk, _ in keys])
        pk_rows.append([pk for _, pk in keys])
    hx, hy, hok = bn.g1_to_limbs(h_pts)
    sigx, sigy, sig_mask = bn.g1_committee_to_limbs(sig_rows, len(keys))
    pkx, pky, pk_mask = bn.g2_committee_to_limbs(pk_rows, len(keys))

    # distinct replay data per shard: one funded sender pays a recipient
    priv = [(int(rng.integers(1, 2 ** 31)) * 2663 + shard) % secp256k1.N or 1
            for shard in range(s)]
    shard_txs, genesis, coinbases = [], [], []
    coinbase = Address20(b"\xc0" * 20)
    for shard in range(s):
        sender_addr = secp256k1.priv_to_address(priv[shard])
        recipient = Address20(bytes(rng.integers(1, 255, 20, dtype=np.uint8)))
        txs = [sp.sign_transaction(
            Transaction(nonce=k, gas_price=1, gas_limit=30000, to=recipient,
                        value=1000 + shard, payload=bytes([shard % 256])),
            priv[shard]) for k in range(t)]
        shard_txs.append(txs)
        genesis.append({sender_addr: sp.AccountState(balance=10 ** 9)})
        coinbases.append(coinbase)
    rep = replay_jax.build_replay_inputs(shard_txs, genesis, coinbases,
                                         pad_txs=t)

    inputs = StressInputs(
        has_voted=jnp.zeros((s, committee_size), bool),
        vote_count=jnp.zeros(s, jnp.int32),
        last_submitted=jnp.zeros(s, jnp.int32),
        last_approved=jnp.zeros(s, jnp.int32),
        is_elected=jnp.zeros(s, bool),
        chunk_root=jnp.zeros((s, 32), jnp.uint8),
        new_header=jnp.ones(s, bool),
        new_chunk_root=jnp.asarray(roots),
        att_index=jnp.asarray(att_index),
        att_pool_index=jnp.asarray(att_pool_index),
        att_sender=jnp.asarray(att_sender),
        att_chunk_root=jnp.asarray(att_root),
        att_deposited=jnp.asarray(att_deposited),
        att_valid=jnp.asarray(att_valid),
        hx=jnp.asarray(hx), hy=jnp.asarray(hy),
        sigx=jnp.asarray(sigx), sigy=jnp.asarray(sigy),
        sig_mask=jnp.asarray(sig_mask),
        pkx=jnp.asarray(pkx), pky=jnp.asarray(pky),
        pk_mask=jnp.asarray(pk_mask),
        agg_valid=jnp.asarray(hok),
        addrs=rep.addrs, nonces=rep.nonces, balances=rep.balances,
        coinbase_ix=rep.coinbase_ix,
        tx_e=rep.tx_e, tx_r=rep.tx_r, tx_s=rep.tx_s,
        tx_recid=rep.tx_recid, tx_nonce=rep.tx_nonce,
        tx_gas_limit=rep.tx_gas_limit, tx_intrinsic=rep.tx_intrinsic,
        tx_price=rep.tx_price, tx_value=rep.tx_value, tx_to=rep.tx_to,
        tx_valid=rep.tx_valid,
    )
    return inputs, pool_addr, np.frombuffer(blockhash, np.uint8), \
        sample_size, {"shard_txs": shard_txs}
