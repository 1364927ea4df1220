#!/usr/bin/env python3
"""chip_smoke.py: the served period audit, proven on the chip.

Drives the system's main path once through the entry points a user calls
-- client -> ``python -m gethsharding_tpu.rpc.chain_server --sigbackend
jax`` -> serving queue -> sigbackend dispatch -> device -- at the
protocol's own size: one SMC period at the contract's constants
(sharding_manager.sol: 100 shards, committee 135, quorum 90), i.e. one
``shard_verifyCommittees`` call of 100 rows x 90..135 BLS votes over real
vote digests from registered keys, made from ``--seed``. Every verdict is
compared with the scalar reference (`PythonSigBackend`).

Legs, one chip-holding child process at a time:

  A  served path at module defaults (the pairing check in the Pallas
     Miller and final-exp kernels, the platform's choice): the period
     twice with row keys (cold, then warm) and once without (recompute
     path), then one small request per other kernel family (ecrecover,
     aggregate verify, DAS sample verify, DAS multiproof verify)
  B  every Pallas kernel in gethsharding_tpu/ops/ compiled
     (interpret=False) at the audit's block shape against its XLA twin
  C  the period without row keys under LIMB_FORM=exact: the 22-limb
     form around the same kernels on the served path at full size
  D  leg A's period requests on a four-device mesh (--mesh-devices 4),
     when the first child reported >= 4 devices

THIS process never imports JAX: a parent that touched JAX would hold the
chip its children need. It fails (non-zero exit, no result line) when a
child's platform is not ``tpu``, on any wrong verdict, on a child that
exits non-zero, and on any leg that raises. ``--rehearsal`` is the only
way to exit 0 off the chip: JAX_PLATFORMS=cpu, 2 shards x 3 votes, Pallas
in interpret mode, ``rehearsal`` and ``cpu`` on every line.

Last line of stdout on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import random
import select
import shutil
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# sharding_manager.sol: SHARD_COUNT, COMMITTEE_SIZE, QUORUM_SIZE
SHARDS, COMMITTEE, QUORUM = 100, 135, 90
PERIOD = 1
SCALAR_SAMPLE_ROWS = 4   # period rows recomputed by the scalar reference
MEGA_ENV = {"GETHSHARDING_TPU_LIMB_FORM": "exact"}

# set once in main(); prefixes every line so a rehearsal can never be
# read as a chip run
_TAG = ""


def say(msg: str) -> None:
    print(f"{_TAG}{msg}", flush=True)


# == workload (host scalar crypto only; made from --seed) ===================


def _sign_row(task):
    """Pool worker: one row's votes. (digest, [sk...]) -> [G1 sig...]."""
    from gethsharding_tpu.crypto import bn256

    digest, sks = task
    return [bn256.bls_sign(digest, sk) for sk in sks]


def build_workload(seed: int, shards: int, committee: int, quorum: int,
                   workers: int) -> dict:
    """One period of votes, made from `seed` through the protocol's own
    objects: `committee` notaries registered on a SimulatedMainchain
    (derived BLS keys + proofs of possession), one collation root and
    vote digest per shard, per-row attendance drawn in quorum..committee
    (ragged masks, distinct cache keys), every vote BLS-signed with the
    voter's registered key. Row 0 has full attendance (so the batch pads
    to the committee's width), one row carries a forged vote and one row
    is empty."""
    import multiprocessing

    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.mainchain.accounts import AccountManager
    from gethsharding_tpu.params import ETHER, Config
    from gethsharding_tpu.smc.chain import SimulatedMainchain
    from gethsharding_tpu.smc.state_machine import vote_digest
    from gethsharding_tpu.utils.hexbytes import Hash32

    rng = random.Random(seed)
    chain = SimulatedMainchain(config=Config(
        shard_count=shards, committee_size=committee, quorum_size=quorum))
    manager = AccountManager()
    accounts = [manager.new_account(seed=b"chip-smoke-%d-notary-%d"
                                    % (seed, i)) for i in range(committee)]
    for acct in accounts:
        chain.fund(acct.address, 2000 * ETHER)
        chain.register_notary(
            acct.address, bls_pubkey=acct.bls_pubkey,
            bls_pop=manager.bls_proof_of_possession(acct.address))
    registry = chain.smc.notary_registry
    pubkeys = [registry[acct.address].bls_pubkey for acct in accounts]
    sks = [acct.bls_keypair()[0] for acct in accounts]

    # a two-row rehearsal has room for the forged row only
    special = rng.sample(range(1, shards), min(2, shards - 1))
    forged_row = special[0]
    empty_row = special[1] if len(special) > 1 else None
    digests, voters = [], []
    for s in range(shards):
        root = Hash32(keccak256(b"chip-smoke-%d-root-%d" % (seed, s)))
        digests.append(bytes(vote_digest(s, PERIOD, root)))
        attend = committee if s == 0 else rng.randint(quorum, committee)
        voters.append([] if s == empty_row
                      else sorted(rng.sample(range(committee), attend)))
    tasks = [(digests[s], [sks[i] for i in voters[s]])
             for s in range(shards)]
    if workers > 1:
        # spawn, never fork: the workers import only the scalar crypto
        with multiprocessing.get_context("spawn").Pool(workers) as pool:
            sig_rows = pool.map(_sign_row, tasks, chunksize=1)
    else:
        sig_rows = [_sign_row(task) for task in tasks]
    # the forged vote: a registered voter's real signature over ANOTHER
    # shard's digest, a well-formed G1 point that does not verify here
    other = digests[(forged_row + 1) % shards]
    sig_rows[forged_row][0] = manager.bls_sign(
        accounts[voters[forged_row][0]].address, other)
    expected = [s not in (forged_row, empty_row) for s in range(shards)]
    return {
        "manager": manager, "accounts": accounts,
        "messages": digests, "sig_rows": sig_rows,
        "pk_rows": [[pubkeys[i] for i in row] for row in voters],
        # the cache key: the ordered voter indices determine the row's
        # pubkeys (the notary's int-tuple idiom)
        "pk_row_keys": [("chip-smoke", seed) + tuple(row)
                        for row in voters],
        "expected": expected,
        "forged_row": forged_row, "empty_row": empty_row,
        "sigs": sum(len(r) for r in sig_rows),
    }


def scalar_check_period(work: dict, seed: int) -> list:
    """The scalar reference on the rows False by construction plus a
    seeded sample: returns the checked row indices after asserting the
    reference agrees with the construction."""
    from gethsharding_tpu.sigbackend import PythonSigBackend

    rng = random.Random(seed ^ 0x5CA1A5)
    rows = {work["forged_row"], work["empty_row"]} - {None}
    pool = [i for i in range(len(work["messages"])) if i not in rows]
    rows.update(rng.sample(pool, min(SCALAR_SAMPLE_ROWS, len(pool))))
    rows = sorted(rows)
    got = PythonSigBackend().bls_verify_committees(
        [work["messages"][i] for i in rows],
        [work["sig_rows"][i] for i in rows],
        [work["pk_rows"][i] for i in rows])
    want = [work["expected"][i] for i in rows]
    if got != want:
        raise AssertionError(
            f"scalar reference disagrees with the workload's construction "
            f"on rows {rows}: {got} != {want}")
    return rows


def build_small_requests(work: dict, seed: int, n_ecrecover: int,
                         k_samples: int) -> list:
    """One small request per remaining kernel family, each with its
    scalar-reference answer: [(label, backend method, args, want)]."""
    from gethsharding_tpu.crypto import bn256
    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.das import pcs
    from gethsharding_tpu.das.erasure import extend_body
    from gethsharding_tpu.das.proofs import (chunk_leaf, merkle_levels,
                                             merkle_proof)
    from gethsharding_tpu.sigbackend import PythonSigBackend

    rng = random.Random(seed ^ 0xD15EA5E)
    manager, accounts = work["manager"], work["accounts"]
    scalar = PythonSigBackend()
    out = []

    # shard_ecrecover: tx-sender recovery, one malformed signature
    digests = [keccak256(b"chip-smoke-%d-tx-%d" % (seed, i))
               for i in range(n_ecrecover)]
    sigs = [manager.sign_hash(accounts[i % len(accounts)].address, d)
            for i, d in enumerate(digests)]
    sigs[n_ecrecover // 2] = sigs[n_ecrecover // 2][:64] + b"\x09"
    args = (digests, sigs)
    out.append((f"shard_ecrecover x{n_ecrecover}", "ecrecover_addresses",
                args, scalar.ecrecover_addresses(*args)))

    # shard_verifyAggregates: row 0's votes aggregated host-side
    args = ([work["messages"][0]],
            [bn256.bls_aggregate_sigs(work["sig_rows"][0])],
            [bn256.bls_aggregate_pks(work["pk_rows"][0])])
    out.append(("shard_verifyAggregates x1", "bls_verify_aggregates",
                args, scalar.bls_verify_aggregates(*args)))

    # shard_dasVerify: k sampled chunks of one extended body, one bad path
    body = rng.randbytes(48 * 4096)
    xb = extend_body(body)
    levels = merkle_levels([chunk_leaf(c) for c in xb.chunks])
    root = levels[-1][0]
    picks = rng.sample(range(xb.n), k_samples)
    proofs = [list(merkle_proof(levels, i)) for i in picks]
    proofs[-1][0] = bytes(32)
    args = ([xb.chunks[i] for i in picks], picks, proofs,
            [root] * k_samples)
    out.append((f"shard_dasVerify k={k_samples}", "das_verify_samples",
                args, scalar.das_verify_samples(*args)))

    # shard_dasPolyVerify: one collation's multiproof over k indices
    values = [pcs.chunk_value(c) for c in xb.chunks]
    commitment = pcs.g1_to_bytes(pcs.commit(values))
    proof, evals = pcs.open_multi(values, picks)
    args = ([commitment], [picks], [evals], [pcs.g1_to_bytes(proof)],
            [len(values)])
    out.append(("shard_dasPolyVerify x1", "das_verify_multiproofs",
                args, scalar.das_verify_multiproofs(*args)))
    return out


# == children (one chip holder at a time) ===================================


class Children:
    """Starts and stops the chip-holding children, at most one alive."""

    def __init__(self):
        self.current = None
        self._buf = b""

    def start(self, cmd, env):
        if self.current is not None and self.current.poll() is None:
            raise RuntimeError("a chip-holding child is still alive")
        self._buf = b""
        self.current = subprocess.Popen(
            cmd, env=env, cwd=REPO, stdout=subprocess.PIPE, bufsize=0)
        return self.current

    def read_json_line(self, timeout_s: float) -> dict:
        """The next JSON object line of the child's stdout (its banner
        or result); raises if the child exits or the deadline passes
        first. Reads the raw pipe so `select` sees every byte."""
        proc = self.current
        deadline = time.monotonic() + timeout_s
        while True:
            while b"\n" in self._buf:
                line, _, self._buf = self._buf.partition(b"\n")
                if line.lstrip().startswith(b"{"):
                    return json.loads(line)
            left = deadline - time.monotonic()
            if left <= 0:
                raise TimeoutError(
                    f"no line from the child within {timeout_s:.0f} s")
            ready, _, _ = select.select([proc.stdout], [], [], left)
            if ready:
                chunk = os.read(proc.stdout.fileno(), 65536)
                if not chunk:
                    raise RuntimeError(
                        f"child exited (code {proc.wait()}) before its line")
                self._buf += chunk

    def stop(self) -> int:
        """SIGINT (the server's clean shutdown), then SIGTERM, then wait:
        a SIGKILLed chip holder can leave the chip locked, so SIGKILL is
        the last resort and fails the run."""
        proc, self.current = self.current, None
        if proc is None:
            return 0
        for sig, grace in ((signal.SIGINT, 60), (signal.SIGTERM, 30)):
            if proc.poll() is not None:
                break
            proc.send_signal(sig)
            try:
                proc.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                continue
        if proc.poll() is None:
            proc.kill()
            proc.wait()
            raise RuntimeError("child ignored SIGINT and SIGTERM")
        return proc.returncode


def _child_env(rehearsal: bool, extra: dict) -> dict:
    env = dict(os.environ)
    # every child measures ITS leg's knobs and nothing ambient
    for key in list(env):
        if key.startswith("GETHSHARDING_TPU_"):
            del env[key]
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def _check_device(leg: str, device, rehearsal: bool) -> None:
    if not device:
        raise RuntimeError(f"leg {leg}: child reported no device record")
    want = "cpu" if rehearsal else "tpu"
    if device["platform"] != want:
        raise RuntimeError(
            f"leg {leg}: child runs on platform {device['platform']!r}, "
            f"not {want!r}" + ("" if rehearsal else
                               " (off the chip only --rehearsal passes)"))
    say(f"leg {leg}: platform={device['platform']} "
        f"device_kind={device['device_kind']!r} count={device['count']} "
        f"(as reported by the child) "
        f"compile_cache_dir={device['compile_cache_dir']}")


def _cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except OSError:  # not created yet: nothing compiled so far
        return 0


def _counter(snapshot: dict, name: str):
    row = snapshot.get(name)
    if row is None:
        return None
    return row.get("count", row.get("value"))


# == the served legs (A, C, D) ==============================================


# the period's three requests: with row keys cold (line tables and pk
# planes precomputed and cached on the device), the same again warm, and
# without keys (the recompute path)
KEYED_COLD, KEYED_WARM, KEYLESS = "keyed cold", "keyed warm", "keyless"


def served_leg(leg: str, children: Children, get_work, args,
               env_extra: dict, requests=(KEYED_COLD, KEYED_WARM, KEYLESS),
               small_requests: bool = False, mesh_devices=None,
               reference=None) -> dict:
    """Start one chain_server child, drive the period `requests` (and
    the small ones, leg A) through `RpcReplicaBackend`, check every
    verdict, read the child's metrics and health, stop the child. The
    workload is built (once) only after the child named its device: off
    the chip the run fails in seconds, not after minutes of signing."""
    from gethsharding_tpu.fleet.router import RpcReplicaBackend

    cmd = [sys.executable, "-m", "gethsharding_tpu.rpc.chain_server",
           # plain jax, not failover-jax: a breaker that quietly serves
           # scalar verdicts would hide a device fault
           "--sigbackend", "jax"]
    if mesh_devices:
        cmd += ["--mesh-devices", str(mesh_devices)]
    t_boot = time.monotonic()
    children.start(cmd, _child_env(args.rehearsal, env_extra))
    try:
        banner = children.read_json_line(args.boot_timeout)
        boot_s = time.monotonic() - t_boot
        device = banner.get("device")
        _check_device(leg, device, args.rehearsal)
        if mesh_devices and device["count"] < mesh_devices:
            raise RuntimeError(f"leg {leg}: {device['count']} devices in the "
                               f"record, wanted {mesh_devices}")
        entries0 = _cache_entries(device["compile_cache_dir"])
        work = get_work()
        small = (build_small_requests(
            work, args.seed, n_ecrecover=4 if args.rehearsal else 64,
            k_samples=4 if args.rehearsal else 16)
            if small_requests else [])
        backend = RpcReplicaBackend.dial(banner["host"], banner["port"],
                                         timeout=args.rpc_timeout)
        try:
            period = (work["messages"], work["sig_rows"], work["pk_rows"])
            verdicts = None
            for label in requests:
                keys = None if label == KEYLESS else work["pk_row_keys"]
                t0 = time.monotonic()
                got = backend.bls_verify_committees(*period,
                                                    pk_row_keys=keys)
                wall = time.monotonic() - t0
                if got != work["expected"]:
                    bad = [i for i, (g, w) in
                           enumerate(zip(got, work["expected"])) if g != w]
                    raise AssertionError(
                        f"leg {leg}: {label} period verdicts differ from "
                        f"the scalar reference on rows {bad}")
                if verdicts is None:
                    say(f"leg {leg}: first verdict ({label}) {wall:.1f} s "
                        f"after the request (compile + set-up; server boot "
                        f"{boot_s:.1f} s before it)")
                else:
                    say(f"leg {leg}: {label} period request {wall:.3f} s "
                        f"(incl. compile when cold; orientation, not a "
                        f"measurement)")
                verdicts = got
            if reference is not None and verdicts != reference:
                raise AssertionError(
                    f"leg {leg}: verdicts differ from leg A's")
            say(f"leg {leg}: period x{len(requests)} ok ({len(verdicts)} "
                f"rows, {work['sigs']} signatures; forged row "
                f"{work['forged_row']} and empty row {work['empty_row']} "
                f"rejected)")
            for label, method, call_args, want in small:
                t0 = time.monotonic()
                got = getattr(backend, method)(*call_args)
                if list(got) != list(want):
                    raise AssertionError(
                        f"leg {leg}: {label} differs from the scalar "
                        f"reference: {got} != {want}")
                say(f"leg {leg}: {label} ok "
                    f"({time.monotonic() - t0:.1f} s incl. compile)")
            if mesh_devices:
                _check_mesh(leg, backend, mesh_devices)
            snap = backend.metrics()
            health = backend.health()
        finally:
            backend.close()
        if health.get("device") != device:
            raise AssertionError(
                f"leg {leg}: shard_health device record {health.get('device')}"
                f" differs from the banner's {device}")
        suspects = _counter(snap, "perfwatch/timer_suspect")
        say(f"leg {leg}: perfwatch/timer_suspect={suspects} "
            f"jax/compile_cache/hits="
            f"{_counter(snap, 'jax/compile_cache/hits')} misses="
            f"{_counter(snap, 'jax/compile_cache/misses')} "
            f"(per-shape, this process); persistent cache entries "
            f"{entries0} -> "
            f"{_cache_entries(device['compile_cache_dir'])}")
    finally:
        rc = children.stop()
    # -2: the SIGINT that stops a server whose handler did not yet run
    if rc not in (0, -signal.SIGINT):
        raise RuntimeError(f"leg {leg}: chain_server exited {rc}")
    return {"device": device, "verdicts": verdicts}


def _check_mesh(leg: str, backend, n: int) -> None:
    """Leg D's non-vacuity: every device holds bytes, every cache shard
    is populated. The devscope poller publishes on its own interval, so
    poll the metrics until it has."""
    deadline = time.monotonic() + 60
    while True:
        snap = backend.metrics()
        in_use = [_counter(snap, f"devscope/mem/d{i}/bytes_in_use")
                  for i in range(n)]
        if all(v for v in in_use) or time.monotonic() > deadline:
            break
        time.sleep(1.0)
    shards = [_counter(snap, f"jax/pk_device_cache/shard{i}/bytes")
              for i in range(n)]
    say(f"leg {leg}: per-device bytes_in_use={in_use} "
        f"cache shard bytes={shards}")
    if not all(v for v in in_use):
        raise AssertionError(f"leg {leg}: a device holds no bytes")
    if not all(v for v in shards):
        raise AssertionError(f"leg {leg}: a cache shard is empty")
    collectives = _counter(snap, "jax/mesh/collectives")
    say(f"leg {leg}: collectives per compiled mesh step, as the code "
        f"counts them from the HLO: {collectives}")


# == leg B: the Pallas kernels, compiled ====================================


def kernel_leg(children: Children, args) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--kernel-child"]
    if args.rehearsal:
        cmd.append("--rehearsal")
    proc = children.start(cmd, _child_env(args.rehearsal, {}))
    try:
        banner = children.read_json_line(args.boot_timeout)
        _check_device("B", banner["device"], args.rehearsal)
        result = children.read_json_line(args.rpc_timeout)
        rc = proc.wait(timeout=120)
    finally:
        children.stop()
    for name, row in result["kernels"].items():
        say(f"leg B: {name}: matches its XLA twin={row['ok']} "
            f"({row['wall_s']:.1f} s incl. compile)")
    if rc != 0 or not all(r["ok"] for r in result["kernels"].values()):
        raise AssertionError(f"leg B: kernel child failed (exit {rc})")
    return {"device": banner["device"]}


def kernel_child(rehearsal: bool) -> int:
    """The one process of leg B: imports JAX, holds the chip, runs every
    `pl.pallas_call` site of gethsharding_tpu/ops/ compiled against its
    XLA twin on real committee votes (one valid row, one tampered).
    Independent compiles run on threads (XLA compiles off the GIL)."""
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from gethsharding_tpu.ops import device as device_mod

    print(json.dumps({"device": device_mod.device_record()}), flush=True)

    import jax
    import jax.numpy as jnp

    from gethsharding_tpu.crypto import bn256 as ref
    from gethsharding_tpu.ops import bn256_jax as k
    from gethsharding_tpu.ops import pallas_finalexp as mega

    interpret = rehearsal  # compiled on the chip, interpreted on the CPU
    # three committee chunks of the aggregation kernel's block (its block
    # shape does not depend on the width; the XLA twin's compile time
    # does), 5 real voters; two rows pad to one 128-lane block like the
    # audit's 112
    width, voters = (8, 3) if rehearsal else (3 * mega.AGG_CHUNK, 5)
    tag = b"chip-smoke-kernels"
    keys = [ref.bls_keygen(tag + bytes([j])) for j in range(voters)]
    sigs = [ref.bls_sign(tag, sk) for sk, _ in keys]
    tampered = sigs[:-1] + [ref.g1_add(sigs[-1], ref.G1_GEN)]
    pks = [pk for _, pk in keys]
    hx, hy, _ = k.g1_to_limbs([ref.hash_to_g1(tag)] * 2)
    g1 = tuple(map(jnp.asarray,
                   k.g1_committee_to_limbs([sigs, tampered], width)))
    g2 = tuple(map(jnp.asarray,
                   k.g2_committee_to_limbs([pks, pks], width)))
    hx, hy = jnp.asarray(hx), jnp.asarray(hy)
    agg_g1_twin = jax.jit(k.aggregate_g1_proj)
    agg_g2_twin = jax.jit(k.aggregate_g2_proj)

    def same_point(mul, eq, want, got):
        # projective equality by cross-multiplication, Z != 0 first
        return (eq(mul(want[0], got[2]), mul(got[0], want[2]))
                & eq(mul(want[1], got[2]), mul(got[1], want[2])))

    def agg_g1():
        want = agg_g1_twin(*g1)
        got = mega.aggregate_proj(*g1, fp2=False, interpret=interpret)
        live = ~np.asarray(jax.jit(k.FP.is_zero)(got[2]))
        same = jax.jit(lambda w, g: same_point(k.FP.mul, k.FP.eq, w, g))(
            want, got)
        return bool(live.all() and np.asarray(same).all())

    def agg_g2():
        want = agg_g2_twin(*g2)
        got = mega.aggregate_proj(*g2, fp2=True, interpret=interpret)
        same = jax.jit(lambda w, g: same_point(k.fp2_mul, k.fp2_eq, w, g))(
            want, got)
        return bool(np.asarray(same).all())

    # the twins are the XLA forms the platform would not choose here
    miller_twin = jax.jit(
        lambda s, hx, hy, p: k._bls_miller_opt(s, hx, hy, p, pallas=False))

    with ThreadPoolExecutor(max_workers=8) as pool:
        def timed(fn):
            def run():
                t0 = time.perf_counter()
                ok = fn()
                return {"ok": bool(ok),
                        "wall_s": round(time.perf_counter() - t0, 2)}
            return pool.submit(run)

        # the Miller and final-exp twins feed on the XLA aggregates
        f_twin = pool.submit(
            lambda: miller_twin(agg_g1_twin(*g1), hx, hy, agg_g2_twin(*g2)))

        def miller():
            got = mega.miller_f(agg_g1_twin(*g1), hx, hy, agg_g2_twin(*g2),
                                interpret=interpret)
            same = jax.jit(k.fp12_eq)(f_twin.result(), got)
            return bool(np.asarray(same).all())

        def finalexp():
            f = f_twin.result()
            want = [bool(b) for b in np.asarray(jax.jit(
                functools.partial(k.pairing_is_one, pallas=False))(f))]
            got = [bool(b) for b in np.asarray(
                mega.finalexp_is_one(f, interpret=interpret))]
            return want == got == [True, False]

        futures = {
            "aggregation G1": timed(agg_g1),
            "aggregation G2": timed(agg_g2),
            "Miller loop": timed(miller),
            "final exponentiation": timed(finalexp),
        }
        # every future's result is read: a kernel that raised (a Mosaic
        # refusal) fails the child with its traceback
        kernels = {name: fut.result() for name, fut in futures.items()}
    print(json.dumps({"kernels": kernels}), flush=True)
    return 0 if all(row["ok"] for row in kernels.values()) else 1


# == main ===================================================================


def main(argv=None) -> int:
    global _TAG
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=21)
    parser.add_argument("--rehearsal", action="store_true",
                        help="sandbox rehearsal: JAX_PLATFORMS=cpu, 2 shards "
                             "x 3 votes, Pallas in interpret mode; the only "
                             "way to exit 0 off the chip")
    parser.add_argument("--legs", default="ABCD",
                        help="subset of legs to run (default ABCD; D runs "
                             "only on >= 4 devices)")
    parser.add_argument("--boot-timeout", type=float, default=300.0,
                        help="seconds a child may take to report its device")
    parser.add_argument("--rpc-timeout", type=float, default=900.0,
                        help="per-request deadline: a cold first request "
                             "compiles for minutes, far past the client's "
                             "10 s default")
    parser.add_argument("--kernel-child", action="store_true",
                        help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.kernel_child:
        return kernel_child(args.rehearsal)

    _TAG = "[rehearsal cpu] " if args.rehearsal else ""
    shards, committee, quorum = ((2, 3, 2) if args.rehearsal
                                 else (SHARDS, COMMITTEE, QUORUM))
    t_start = time.monotonic()

    # built here, before any child starts, so no two processes race the
    # first-use build (gethsharding_tpu/native.py)
    from gethsharding_tpu import native

    say("native library: "
        + ("native/build/libgethsharding.so built from native/*.c and "
           "loaded" if native.available() else
           f"unavailable, pure-Python fallback (cc "
           f"{'found' if shutil.which(os.environ.get('CC', 'cc')) else 'missing'})"))

    @functools.cache
    def get_work():
        t0 = time.monotonic()
        workers = (1 if args.rehearsal
                   else max(1, min(12, os.cpu_count() or 1)))
        work = build_workload(args.seed, shards, committee, quorum, workers)
        checked = scalar_check_period(work, args.seed)
        say(f"set-up: period of {shards} rows x {quorum}..{committee} "
            f"votes, {work['sigs']} signatures from {committee} registered "
            f"keys, seed {args.seed}, {workers} signing worker(s); scalar "
            f"reference on rows {checked}: {time.monotonic() - t0:.1f} s "
            f"(host scalar crypto, not a device time)")
        return work

    children = Children()
    first_device = None
    leg_a = None
    for leg in "ABCD":
        if leg not in args.legs:
            say(f"leg {leg}: not run (--legs {args.legs})")
            continue
        if leg == "A":
            leg_a = out = served_leg("A", children, get_work, args, {},
                                     small_requests=True)
        elif leg == "B":
            out = kernel_leg(children, args)
        elif leg == "C":
            out = served_leg("C", children, get_work, args, MEGA_ENV,
                             requests=(KEYLESS,),
                             reference=leg_a and leg_a["verdicts"])
        else:
            if first_device is not None and first_device["count"] < 4:
                say(f"mesh leg: not run ({first_device['count']} device)")
                continue
            out = served_leg("D", children, get_work, args, {},
                             mesh_devices=4,
                             reference=leg_a and leg_a["verdicts"])
        first_device = first_device or out["device"]

    if first_device is None:
        raise RuntimeError("no leg ran: nothing was proven")
    if "jax" in sys.modules:
        raise RuntimeError("the parent imported JAX: it would have held "
                           "the chip its children need")
    say(f"all legs passed in {time.monotonic() - t_start:.0f} s")
    result = {"ok": True,
              "device": {"platform": first_device["platform"],
                         "kind": first_device["device_kind"],
                         "count": first_device["count"]}}
    if args.rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
