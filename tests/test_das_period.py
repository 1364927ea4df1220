"""The notary's availability vote at a period's shape (ISSUE 35): the
benchmark's `das_period` builder, through the serving tier in process and
through a real `chain_server` socket, every verdict held against the
scalar reference (`das/proofs.verify_samples` over `crypto/keccak.py`).

Tiny shapes on the CPU: the configuration's rehearsal period (2 shards x
2 samples) and one of 130 rows (10 shards x 13 samples), whose batch
bucket of 160 lies over the serving tier's `max_batch` of 128, so the
request is the always-oversized one the 1,600-row period is. Verdicts,
counts and containment only: no time measured here means anything.
"""

import json
import os
import subprocess
import sys

import pytest

from gethsharding_tpu import metrics
from gethsharding_tpu.das import erasure, proofs, sampler
from gethsharding_tpu.sigbackend import PythonSigBackend, bucket_size

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from builders import das_period  # noqa: E402

with open(os.path.join(BENCH, "configs", "das_period_100x16.json")) as _src:
    CONFIG = json.load(_src)
SEED = 3035
OP = "das_verify"
# the oversized shape takes every fault the builder knows, the rehearsal
# shape (4 rows) the configuration's own three
SHAPES = {
    "rehearsal": CONFIG["rehearsal"],
    "oversized": {"rows": 10, "samples": 13, "body_bytes": 9 * 4096,
                  "faults": sorted(das_period.FAULTS)},
}
OVERSIZED_ROWS = 130


def _config(shape):
    return {**CONFIG, **SHAPES[shape]}


@pytest.fixture(scope="module")
def datasets():
    """Both shapes' data sets, each held against the scalar reference on
    every row, as the benchmark's set-up holds them."""
    out = {}
    for shape in SHAPES:
        config = _config(shape)
        data = das_period.build(config, SEED, workers=1)
        data["checked_rows"] = das_period.check(config, data, SEED)
        out[shape] = data
    return out


@pytest.fixture(scope="module")
def serving():
    from gethsharding_tpu.serving import ServingSigBackend
    from gethsharding_tpu.sigbackend import JaxSigBackend

    tier = ServingSigBackend(JaxSigBackend())
    try:
        yield tier
    finally:
        tier.close()


@pytest.fixture(scope="module")
def socket_client(serving):
    """`python -m gethsharding_tpu.rpc.chain_server --sigbackend jax` on
    the CPU behind `RpcReplicaBackend`, the benchmark's client. It starts
    after the in-process tier, whose compiles it finds in the cache."""
    from gethsharding_tpu.fleet.router import RpcReplicaBackend

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.Popen(
        [sys.executable, "-m", "gethsharding_tpu.rpc.chain_server",
         "--sigbackend", "jax", "--port", "0", "--runtime", "900"],
        stdout=subprocess.PIPE, env=env, cwd=REPO, text=True)
    try:
        banner = json.loads(proc.stdout.readline())
        client = RpcReplicaBackend.dial(banner["host"], banner["port"],
                                        timeout=600.0)
        try:
            yield client
        finally:
            client.close()
    finally:
        proc.terminate()
        proc.wait(timeout=30)


@pytest.fixture(scope="module")
def served_verdicts(datasets, serving):
    """Every period of both shapes through the in-process tier, with the
    registry read around the oversized shape's first request."""
    out, counts = {}, {}
    for shape, data in datasets.items():
        out[shape] = []
        for period in data["periods"]:
            before = metrics.DEFAULT_REGISTRY.snapshot()
            out[shape].append(serving.das_verify_samples(
                *das_period.arguments(period)))
            counts.setdefault(shape, (before,
                                      metrics.DEFAULT_REGISTRY.snapshot()))
    return out, counts


# == the configuration's arithmetic, tied to the code's constants ===========


def test_the_configurations_shapes_are_the_codes():
    assert CONFIG["chunk_bytes"] == erasure.DAS_CHUNK_SIZE
    rows = CONFIG["rows"] * CONFIG["samples"]
    assert (rows, bucket_size(rows)) == (1600, 1792)
    data_chunks = CONFIG["body_bytes"] // erasure.DAS_CHUNK_SIZE
    extended = data_chunks + -(-data_chunks // 2)
    assert (data_chunks, extended) == (170, erasure.MAX_TOTAL_CHUNKS)
    # 255 leaves pad to 256: every path has MAX_PROOF_DEPTH siblings
    assert (extended - 1).bit_length() == proofs.MAX_PROOF_DEPTH
    assert set(CONFIG["faults"]) <= set(das_period.FAULTS)


def test_the_references_body_cannot_be_published_in_sampled_mode():
    """A 1 MiB collation body (core/types.py, collation.go:45) needs 384
    extended chunks at the default parity; the GF(2^8) code stops at 255
    (PERF.md section 7). One chunk over the configuration's body fails
    the same way."""
    for size in (1 << 20, CONFIG["body_bytes"] + 1):
        with pytest.raises(erasure.ErasureError):
            erasure.extend_body(b"\x00" * size, CONFIG["parity"])


# == the builder ============================================================


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_reference_holds_every_row_of_the_construction(datasets, shape):
    config, data = _config(shape), datasets[shape]
    n = config["rows"] * config["samples"]
    assert data["checked_rows"] == [[1, n], [2, n]]
    for period in data["periods"]:
        assert len(period["faults"]) == len(config["faults"])
        assert period["expected"].count(False) == len(config["faults"])
        assert period["samples"] == config["samples"]


def test_the_rows_are_the_notarys_own_draw(datasets):
    data = datasets["oversized"]
    period = data["periods"][1]
    per, n = period["samples"], period["chunks_per_body"]
    faulty = {shard * per + slot for _, shard, slot in period["faults"]}
    for shard in range(_config("oversized")["rows"]):
        rows = range(shard * per, (shard + 1) * per)
        root = period["roots"][rows[0]]
        want = sampler.sample_indices(
            sampler.sample_seed(data["account"], shard, period["period"],
                                root), per, n)
        assert [period["indices"][r] for r in rows if r not in faulty] \
            == [i for r, i in zip(rows, want) if r not in faulty]
    assert data["periods"][0]["roots"] != period["roots"]


# == the served path against the reference ==================================


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_serving_tier_agrees_with_the_reference_on_every_row(
        datasets, served_verdicts, shape):
    reference = PythonSigBackend()
    for period, got in zip(datasets[shape]["periods"],
                           served_verdicts[0][shape]):
        assert got == reference.das_verify_samples(
            *das_period.arguments(period)) == period["expected"]


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_chain_server_socket_agrees_with_the_reference_on_every_row(
        datasets, socket_client, shape):
    for period in datasets[shape]["periods"]:
        got = socket_client.das_verify_samples(*das_period.arguments(period))
        assert got == period["expected"]
        shards = das_period.shard_verdicts(period, got)
        assert shards.count(False) == len({s for _, s, _ in
                                           period["faults"]})


@pytest.mark.parametrize("kind", sorted(das_period.FAULTS))
def test_a_fault_costs_its_rows_verdict_and_never_an_error(
        datasets, served_verdicts, kind):
    for period, got in zip(datasets["oversized"]["periods"],
                           served_verdicts[0]["oversized"]):
        (shard, slot), = [(s, t) for k, s, t in period["faults"]
                          if k == kind]
        row = shard * period["samples"] + slot
        assert got[row] is False
        assert das_period.shard_verdicts(period, got)[shard] is False
        # its neighbours in the shard are untouched unless faulty too
        assert got.count(False) == len(period["faults"])


# == one oversized request, one dispatch ====================================


def _delta(counts, name, field="count"):
    before, after = counts
    return (after.get(name) or {}).get(field, 0) \
        - (before.get(name) or {}).get(field, 0)


def test_an_oversized_request_is_one_dispatch(serving, served_verdicts):
    counts = served_verdicts[1]["oversized"]
    assert bucket_size(OVERSIZED_ROWS) == 160 > serving.config.max_batch
    assert _delta(counts, f"serving/{OP}/requests") == 1
    assert _delta(counts, f"serving/{OP}/dispatches") == 1
    assert _delta(counts, f"serving/{OP}/request_rows") == OVERSIZED_ROWS


@pytest.mark.parametrize("name", [
    "sig/host_marshal_time", "sig/transfer_time", "sig/marshal_time",
    "sig/launch_time", "sig/block_time", "sig/pull_time",
    "sig/device_time"])
def test_the_dispatch_enters_every_stage_once(served_verdicts, name):
    assert _delta(served_verdicts[1]["oversized"], name) == 1


def test_the_chunk_planes_bytes_are_counted_with_their_padding(
        served_verdicts):
    counts = served_verdicts[1]["oversized"]
    chunk_bytes = 160 * erasure.DAS_CHUNK_SIZE
    assert _delta(counts, "das/wire/chunk_bytes") == chunk_bytes
    # the chunk plane and, per row, 8 siblings, 16 flags, a root, a flag
    assert _delta(counts, "jax/wire/bytes") == chunk_bytes + 160 * (
        proofs.MAX_PROOF_DEPTH * 32 + 2 * proofs.MAX_PROOF_DEPTH + 32 + 1)


# == the marshal: one copy a plane, against the row-by-row form it had ======


def _marshal_rows(chunks, indices, paths, roots, bucket):
    """`marshal_samples` as it was before ISSUE 35: one copy a row and a
    sibling. Kept as the reference of the planes, bit for bit."""
    import numpy as np

    out = {"chunks": np.zeros((bucket, erasure.DAS_CHUNK_SIZE), np.uint8),
           "sibs": np.zeros((bucket, proofs.MAX_PROOF_DEPTH, 32), np.uint8),
           "bits": np.zeros((bucket, proofs.MAX_PROOF_DEPTH), bool),
           "levels": np.zeros((bucket, proofs.MAX_PROOF_DEPTH), bool),
           "roots": np.zeros((bucket, 32), np.uint8),
           "valid": np.zeros((bucket,), bool), "rows": len(chunks)}
    for b, (chunk, index, path, root) in enumerate(zip(chunks, indices,
                                                       paths, roots)):
        if not isinstance(index, int):
            continue
        if (len(chunk) != erasure.DAS_CHUNK_SIZE or len(root) != 32
                or index < 0 or len(path) > proofs.MAX_PROOF_DEPTH
                or index >> len(path) or any(len(s) != 32 for s in path)):
            continue
        out["chunks"][b] = np.frombuffer(chunk, np.uint8)
        for level, sibling in enumerate(path):
            out["sibs"][b, level] = np.frombuffer(sibling, np.uint8)
            out["bits"][b, level] = bool((index >> level) & 1)
            out["levels"][b, level] = True
        out["roots"][b] = np.frombuffer(root, np.uint8)
        out["valid"][b] = True
    return out


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_planes_equal_the_row_by_row_marshals(datasets, shape):
    import numpy as np

    for period in datasets[shape]["periods"]:
        chunks, indices, paths, roots = map(
            list, das_period.arguments(period))
        # rows no builder makes: no root, a negative and a non-numeric
        # index, a path one sibling too long, no path at all
        roots[0], indices[1] = b"", -1
        paths[2] = tuple(paths[2]) + (b"\x07" * 32,) * (
            proofs.MAX_PROOF_DEPTH + 1 - len(paths[2]))
        paths[3], indices[3] = (), 0
        args = (chunks, indices, paths, roots)
        bucket = bucket_size(len(chunks))
        got = proofs.marshal_samples(*args, bucket)
        want = _marshal_rows(*args, bucket)
        assert sorted(got) == sorted(want)
        for name in ("chunks", "sibs", "bits", "levels", "roots", "valid"):
            assert got[name].dtype == want[name].dtype, name
            assert np.array_equal(got[name], want[name]), name
        assert got["rows"] == want["rows"] == len(chunks)
        assert not got["valid"][:3].any()
        assert proofs.marshal_samples(chunks, ["x"] * len(chunks), paths,
                                      roots, bucket)["valid"].sum() == 0
