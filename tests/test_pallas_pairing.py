"""The pairing check's kernels are chosen by the platform, on the CPU.

On every platform but the CPU the projective Miller walk and the final
exponentiation run as `ops/pallas_finalexp.miller_f` and
`finalexp_is_one` (`bn256_jax.pairing_in_pallas`); a caller that passes
`pallas=False` (every mesh step: a `pallas_call` inside `shard_map`
fails at trace) gets the XLA forms. The platform is `jax.default_backend`
patched to "tpu" and the kernels are spies, so that each entry point is
only TRACED here (`jax.eval_shape` over a fresh function, never a cached
trace) and nothing compiles; `sig/pairing/pallas_rows` is read over
dispatches whose program is stubbed.
"""

import jax
import jax.numpy as jnp
import pytest

from gethsharding_tpu import metrics
from gethsharding_tpu.crypto import bn256 as bls
from gethsharding_tpu.ops import bn256_jax as k
from gethsharding_tpu.ops import pallas_finalexp
from gethsharding_tpu.ops.limb import NLIMBS

PALLAS_ROWS = "sig/pairing/pallas_rows"


def _plane(*shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _entry_points():
    """(name, entry point, argument shapes, kernels it runs off the CPU)
    for the four kernels the backend jits, at one row and one vote."""
    b, rows, votes, steps = jnp.bool_, 1, 1, len(k._OPT_OPS)
    fp = _plane(rows, NLIMBS)
    fp2 = _plane(rows, 2, NLIMBS)
    g1s = (_plane(rows, votes, NLIMBS),) * 2 + (_plane(rows, votes, dtype=b),)
    flag = _plane(rows, dtype=b)
    terms, windows = 2, k.MSM_WINDOWS
    digits = jnp.uint8
    table = (1 << k.MSM_WINDOW) * windows * (terms + 1)
    return {
        "committee": (
            k.bls_aggregate_verify_committee_batch,
            (fp, fp) + g1s + (_plane(rows, votes, 2, NLIMBS),) * 2
            + (_plane(rows, votes, dtype=b), flag),
            {"miller_f", "finalexp_is_one"}),
        "precomp": (
            k.bls_verify_committee_precomp_batch,
            (fp, fp) + g1s + (_plane(rows, steps, 3, 2, NLIMBS), flag, flag),
            {"finalexp_is_one"}),
        "poly": (
            k.das_poly_verify_batch,
            (fp, fp, flag, fp, fp, flag,
             _plane(rows, terms, windows, dtype=digits),
             _plane(rows, terms + 1, windows, dtype=digits), flag,
             _plane(table, 3, NLIMBS), _plane(table, 3, 2, NLIMBS)),
            {"miller_f", "finalexp_is_one"}),
        "aggregate": (
            k.bls_verify_aggregate_batch,
            (fp, fp, fp, fp, fp2, fp2, flag),
            {"finalexp_is_one"}),
    }


ENTRY_POINTS = sorted(_entry_points())


@pytest.fixture
def kernel_calls(monkeypatch):
    """Spies in place of the two kernels: each records its call and
    returns zeros of the shape the kernel would."""
    calls = []

    def miller_f(sig, hx, hy, pk, *, interpret=False):
        calls.append("miller_f")
        return jnp.zeros(sig[0].shape[:-1] + (6, 2, NLIMBS), jnp.int32)

    def finalexp_is_one(f, *, interpret=False):
        calls.append("finalexp_is_one")
        return jnp.zeros(f.shape[:-3], jnp.bool_)

    monkeypatch.setattr(pallas_finalexp, "miller_f", miller_f)
    monkeypatch.setattr(pallas_finalexp, "finalexp_is_one", finalexp_is_one)
    return calls


def _platform(monkeypatch, name):
    monkeypatch.setattr(jax, "default_backend", lambda: name)


def _trace(name, **kw):
    fn, shapes, _ = _entry_points()[name]
    jax.eval_shape(lambda *a: fn(*a, **kw), *shapes)


@pytest.mark.parametrize("name", ENTRY_POINTS)
def test_a_tpu_traces_the_pallas_kernels(monkeypatch, kernel_calls, name):
    """The table-fed walk and the affine walk stay XLA's: those two
    programs take the final exponentiation's kernel alone."""
    _platform(monkeypatch, "tpu")
    _trace(name)
    assert sorted(kernel_calls) == sorted(_entry_points()[name][2])


@pytest.mark.parametrize("name", ENTRY_POINTS)
def test_pallas_false_traces_neither_kernel(monkeypatch, kernel_calls, name):
    """The mesh's argument: the XLA walk and final exponentiation on a
    platform that would choose the kernels."""
    _platform(monkeypatch, "tpu")
    _trace(name, pallas=False)
    assert kernel_calls == []


@pytest.mark.parametrize("name", ENTRY_POINTS)
def test_the_cpu_traces_neither_kernel(monkeypatch, kernel_calls, name):
    _platform(monkeypatch, "cpu")
    _trace(name)
    assert kernel_calls == []


def test_the_mesh_steps_trace_neither_kernel(monkeypatch, kernel_calls):
    """`_mesh_step`, `_mesh_step_precomp` and the partitioned multiproof
    program of a two-device backend pass `pallas=False`."""
    from gethsharding_tpu.sigbackend import JaxSigBackend

    backend = JaxSigBackend(mesh_devices=2)
    _platform(monkeypatch, "tpu")
    shapes = {name: shapes for name, (_, shapes, _) in
              _entry_points().items()}

    def rows(planes, n=2):
        return [_plane(n, *p.shape[1:], dtype=p.dtype) for p in planes]

    jax.eval_shape(backend._bls_committee_mesh, *rows(shapes["committee"]))
    jax.eval_shape(backend._bls_committee_mesh_precomp,
                   *rows(shapes["precomp"]),
                   _plane(len(k._OPT_OPS), 3, 2, NLIMBS))
    poly = shapes["poly"]
    jax.eval_shape(backend._das_poly_mesh, *rows(poly[:9]), *poly[9:])
    assert kernel_calls == []


# == sig/pairing/pallas_rows ================================================


def _committee_call():
    keys = [bls.bls_keygen(b"pallas-rows-%d" % i) for i in range(2)]
    msgs = [b"pallas-rows-header-%d" % r for r in range(3)]
    sig_rows = [[bls.bls_sign(m, sk) for sk, _ in keys] for m in msgs]
    pk_rows = [[pk for _, pk in keys]] * len(msgs)
    return "bls_verify_committees", (msgs, sig_rows, pk_rows)


def _aggregate_call():
    sk, pk = bls.bls_keygen(b"pallas-rows-aggregate")
    msgs = [b"pallas-rows-a", b"pallas-rows-b"]
    return "bls_verify_aggregates", (msgs, [bls.bls_sign(m, sk) for m in msgs],
                                     [pk, pk])


def _poly_call():
    from gethsharding_tpu.das import pcs

    values = [(5 * i + 1) % pcs.N for i in range(4)]
    proof, evals = pcs.open_multi(values, (0, 3))
    row = (pcs.g1_to_bytes(pcs.commit(values)), [0, 3], evals,
           pcs.g1_to_bytes(proof), 4)
    return "das_verify_multiproofs", tuple(list(col) for col in
                                           zip(row, row, row))


CALLS = {"committee": _committee_call, "aggregate": _aggregate_call,
         "poly": _poly_call}


def _stubbed(backend, seen):
    """The backend with its programs out of the way: `_run` records
    which program a dispatch was handed and returns True verdicts for
    its bucket; the multiproof tables are never built."""
    def run(op, shape, fn, args, booking):
        seen.append(fn)
        return jnp.ones(shape[0], jnp.bool_)

    backend._run = run
    backend._srs_tables = lambda: ()
    return backend


@pytest.mark.parametrize("platform, counted", [("tpu", True),
                                               ("cpu", False)])
@pytest.mark.parametrize("op", sorted(CALLS))
def test_pallas_rows_counts_the_real_rows_of_a_dispatch(monkeypatch, op,
                                                        platform, counted):
    """Rows before bucket padding (3 committee rows go out at bucket 4),
    where the program's pairing check runs in the kernels; 0 where it
    does not."""
    from gethsharding_tpu.sigbackend import JaxSigBackend

    backend = _stubbed(JaxSigBackend(), [])
    method, args = CALLS[op]()
    _platform(monkeypatch, platform)
    counter = metrics.counter(PALLAS_ROWS)
    before = counter.value
    assert len(getattr(backend, method)(*args)) == len(args[0])
    assert counter.value - before == (len(args[0]) if counted else 0)


def test_pallas_rows_stays_zero_on_the_mesh(monkeypatch):
    """A mesh's multiproof dispatch is handed the XLA program and
    counts nothing on a platform that would choose the kernels."""
    from gethsharding_tpu.sigbackend import JaxSigBackend

    seen = []
    backend = _stubbed(JaxSigBackend(mesh_devices=2), seen)
    method, args = _poly_call()
    _platform(monkeypatch, "tpu")
    counter = metrics.counter(PALLAS_ROWS)
    before = counter.value
    assert getattr(backend, method)(*args) == [True] * len(args[0])
    assert seen == [backend._das_poly_mesh]
    assert counter.value == before
    assert backend.last_mesh["n_devices"] == 2
