"""Stage-level cost breakdown of the notary audit kernel on the live
backend (the `--profile` companion: where jax.profiler gives a trace,
this prints attackable numbers per pipeline stage).

Stages of `bls_aggregate_verify_committee_batch` at the bench shape
(100 shards x 135 committee slots):
  aggregate  - masked projective tree reduction of committee G1 sigs
               + G2 pubkeys
  miller     - shared-accumulator optimal-ate Miller loop on the
               aggregates
  final_exp  - inversion-free final-exponentiation check
  full       - the production single-dispatch kernel (all of the above
               fused by XLA)

Timing uses random in-range limb data: every stage is integer-only with
static shapes and no data-dependent control flow, so wall-clock does not
depend on the values. Prints ONE JSON line.

With ``--stacks FILE`` (a collapsed-stack file from the devscope
sampling profiler — ``/profile/stacks`` or ``shard_profileStacks``)
the breakdown also prints a HOST-side top-N table next to the device
stages: self samples per leaf frame plus inclusive samples per frame,
so "the chip spends 60% in miller" and "the host spends 40% in
marshalling" read off one artifact.

Usage: python scripts/tpu_breakdown.py [--shards N] [--committee C]
                                       [--stacks FILE [--stacks-top N]]
Honors the same GETHSHARDING_TPU_* kernel knobs as bench.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_collapsed(text: str):
    """Collapsed-stack lines (``frame;frame;frame count``) ->
    (total_samples, self_counts, inclusive_counts). Malformed lines and
    the sampler's ``[stacks-over-budget]`` overflow marker are skipped;
    inclusive counts credit every frame on a stack once per sample (a
    frame repeated by recursion still counts once)."""
    total = 0
    self_counts: dict = {}
    incl_counts: dict = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("["):
            continue
        stack, _, count_s = line.rpartition(" ")
        try:
            count = int(count_s)
        except ValueError:
            continue
        if not stack:
            continue
        frames = stack.split(";")
        total += count
        leaf = frames[-1]
        self_counts[leaf] = self_counts.get(leaf, 0) + count
        for frame in set(frames):
            incl_counts[frame] = incl_counts.get(frame, 0) + count
    return total, self_counts, incl_counts


def host_topn(text: str, n: int = 10):
    """The host-side top-N rows: ``[{frame, self, self_pct, incl,
    incl_pct}]`` ordered by self samples — what the --stacks table and
    the JSON payload carry."""
    total, self_counts, incl_counts = parse_collapsed(text)
    rows = []
    for frame, count in sorted(self_counts.items(),
                               key=lambda kv: -kv[1])[:n]:
        rows.append({
            "frame": frame,
            "self": count,
            "self_pct": round(100.0 * count / total, 1) if total else 0.0,
            "incl": incl_counts.get(frame, count),
            "incl_pct": round(100.0 * incl_counts.get(frame, count)
                              / total, 1) if total else 0.0,
        })
    return total, rows


def _print_host_table(total: int, rows: list) -> None:
    print(f"# host sampling profile: {total} samples", file=sys.stderr)
    print(f"# {'self%':>6} {'incl%':>6} {'self':>7}  frame",
          file=sys.stderr)
    for row in rows:
        print(f"# {row['self_pct']:>5.1f}% {row['incl_pct']:>5.1f}% "
              f"{row['self']:>7}  {row['frame']}", file=sys.stderr)


def _time(fn, args, repeats=5):
    """Median seconds per call, post-compile.

    Completion is forced with a device->host pull (jax.device_get),
    the one operation that provably waits for the device. The pull adds
    output-transfer time, but stage outputs here are ~100 KB,
    negligible against the stage costs being attributed."""
    out = fn(*args)
    jax_tree_block(out)
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax_tree_block(out)
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples))


def jax_tree_block(out):
    import jax

    jax.device_get(out)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--shards", type=int, default=100)
    parser.add_argument("--committee", type=int, default=135)
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--cpu", action="store_true",
                        help="run on the CPU on purpose (without it, "
                             "a host with no accelerator is an error)")
    parser.add_argument("--stacks", default="",
                        help="collapsed-stack file from the devscope "
                             "sampling profiler (/profile/stacks); prints "
                             "a host-side top-N table next to the device "
                             "breakdown and folds it into the JSON line")
    parser.add_argument("--stacks-top", type=int, default=10,
                        help="rows in the host-side table")
    args = parser.parse_args()

    host_total, host_rows = 0, []
    if args.stacks:
        # parse BEFORE the device work: a bad path must fail fast, not
        # after minutes of kernel compiles
        with open(args.stacks) as fh:
            host_total, host_rows = host_topn(fh.read(), args.stacks_top)
        _print_host_table(host_total, host_rows)

    from gethsharding_tpu.ops import device
    from gethsharding_tpu.parallel.virtual import force_virtual_cpu_devices

    if args.cpu:
        force_virtual_cpu_devices(1)
    # places the compile cache and names the device every number below
    # was taken on; refuses an undeclared CPU fallback (ops/device.py)
    record = device.device_record()

    import jax
    import jax.numpy as jnp

    from gethsharding_tpu.ops import bn256_jax as k

    B, C = args.shards, args.committee
    rng = np.random.default_rng(7)
    # the limb count depends on the active form knob (22 exact/25 wide):
    # read it off the engine instead of assuming
    n_limbs = int(np.asarray(k.FP.one).shape[-1])

    def limbs(*shape):
        return jnp.asarray(rng.integers(0, 1 << 12, shape + (n_limbs,),
                                        dtype=np.int32))

    hx, hy = limbs(B), limbs(B)
    sigx, sigy = limbs(B, C), limbs(B, C)
    pkx, pky = limbs(B, C, 2), limbs(B, C, 2)
    sig_mask = jnp.ones((B, C), bool)
    pk_mask = jnp.ones((B, C), bool)
    valid = jnp.ones((B,), bool)

    agg = jax.jit(lambda sx, sy, sm, px, py, pm: (
        k.aggregate_g1_proj(sx, sy, sm), k.aggregate_g2_proj(px, py, pm)))
    (sX, sY, sZ), (pX, pY, pZ) = agg(sigx, sigy, sig_mask, pkx, pky, pk_mask)

    miller = jax.jit(lambda a, b, c, x, y, d, e, f:
                     k._bls_miller_opt((a, b, c), x, y, (d, e, f)))
    f12 = miller(sX, sY, sZ, hx, hy, pX, pY, pZ)

    finalexp = jax.jit(k.pairing_is_one)
    full = jax.jit(k.bls_aggregate_verify_committee_batch)

    timings = {
        "aggregate": _time(agg, (sigx, sigy, sig_mask, pkx, pky, pk_mask),
                           args.repeats),
        "miller": _time(miller, (sX, sY, sZ, hx, hy, pX, pY, pZ),
                        args.repeats),
        "final_exp": _time(finalexp, (f12,), args.repeats),
        "full": _time(full, (hx, hy, sigx, sigy, sig_mask,
                             pkx, pky, pk_mask, valid), args.repeats),
    }
    # sanity: how long does the same 'full' call appear to take when
    # "timed" with block_until_ready only? A large pull/block ratio
    # would mean the block does not wait and only the pull-timed
    # numbers above are real (the perfwatch timer_suspect check)
    out = full(hx, hy, sigx, sigy, sig_mask, pkx, pky, pk_mask, valid)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = full(hx, hy, sigx, sigy, sig_mask, pkx, pky, pk_mask, valid)
    jax.block_until_ready(out)
    block_timed = time.perf_counter() - t0

    sigs = B * C
    knobs = {key: os.environ.get(key, "") for key in (
        "GETHSHARDING_TPU_LIMB_FORM", "GETHSHARDING_TPU_CARRY",
        "GETHSHARDING_TPU_CONV", "GETHSHARDING_TPU_PAIRCONV",
        "GETHSHARDING_TPU_PALLAS")}
    payload = {
        "platform": record["platform"],
        "device_kind": record["device_kind"],
        "device_count": record["count"],
        "shards": B,
        "committee": C,
        "stage_seconds": timings,
        "stage_pct_of_full": {
            name: round(100 * sec / timings["full"], 1)
            for name, sec in timings.items()},
        "sigs_per_sec_full": round(sigs / timings["full"], 1),
        "full_block_timed_s": round(block_timed, 6),
        "knobs": knobs,
    }
    if args.stacks:
        payload["host_samples"] = host_total
        payload["host_stacks_top"] = host_rows
    print(json.dumps(payload))
    return 0


if __name__ == "__main__":
    sys.exit(main())
