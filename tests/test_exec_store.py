"""The executable store (ISSUE 38): compiled programs on disk, found by
shape, loaded with nothing traced.

On the CPU, the store constructed directly on `tmp_path`:

- a small jitted program round-trips through a file, with a pytree of
  outputs and with 2 x B list arguments (the `line_table_stack` shape);
- the key changes with a shape, a dtype, a weak-type flag, one byte of
  one source file of a copied tree, a ``GETHSHARDING_*`` variable, a
  compiler variable and every field of the toolchain record, and with
  nothing else;
- a truncated, a garbage and a foreign file each cost one
  ``jax/exec_store/errors``, are removed, and the call still answers;
- two writers of one key leave one whole file; a new digest's directory
  removes all but the two most recently used others;
- a backend handed a store launches through what it holds, and a second
  backend on the same store traces nothing: in-process with a small
  program, and in a child process with the pairing kernel itself
  patched to raise.
"""

from __future__ import annotations

import os
import pickle
import shutil
import subprocess
import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gethsharding_tpu import metrics
from gethsharding_tpu.sigbackend import execstore
from gethsharding_tpu.sigbackend.execstore import ExecutableStore

REPO = Path(__file__).resolve().parents[1]
TOOLCHAIN = {"jax": "0.9.0", "jaxlib": "0.9.0", "platform_version": "t1",
             "platform": "cpu", "device_kind": "cpu", "device_count": 8}


def _counts() -> dict:
    return {name: metrics.counter("jax/exec_store/" + name).value
            for name in ("hits", "misses", "errors")}


def _delta(before: dict) -> dict:
    return {name: value - before[name] for name, value in _counts().items()}


@pytest.fixture
def package(tmp_path):
    """A copied tree small enough to hash in no time."""
    root = tmp_path / "package"
    (root / "ops").mkdir(parents=True)
    (root / "__init__.py").write_bytes(b"VERSION = 1\n")
    (root / "ops" / "kernel.py").write_bytes(b"def f(x):\n    return x\n")
    return root


def _store(tmp_path, package, toolchain=TOOLCHAIN, environ=None, **changes):
    return ExecutableStore(str(tmp_path / "executables"),
                           toolchain={**toolchain, **changes},
                           environ=environ or {}, package_dir=package)


# == the programs ===========================================================


def _tree_out(x, y):
    return {"sum": x + y, "pair": (x * 2, jnp.sum(y, axis=0))}


def _stack(tabs, infs):
    return jnp.stack(tabs), jnp.stack(infs)


def _tree_args():
    return (jnp.arange(12, dtype=jnp.int32).reshape(4, 3),
            jnp.ones((4, 3), jnp.int32))


def _stack_args(rows=3):
    return ([jnp.full((5, 2), i, jnp.int32) for i in range(rows)],
            [jnp.asarray(bool(i % 2)) for i in range(rows)])


PROGRAMS = {"pytree_out": ("tree_out", (4, 3), _tree_out, _tree_args),
            "list_args": ("line_table_stack", (3,), _stack, _stack_args)}


def _same(got, want) -> bool:
    got, want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    return len(got) == len(want) and all(
        np.array_equal(a, b) for a, b in zip(got, want))


# == round trip =============================================================


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_a_program_round_trips_through_a_file(tmp_path, package, program):
    op, shape, body, make_args = PROGRAMS[program]
    fn, args = jax.jit(body), make_args()
    want = body(*args)
    before = _counts()
    first = _store(tmp_path, package).executable(op, shape, fn, args)
    assert _same(first(*args), want)
    assert _delta(before) == {"hits": 0, "misses": 1, "errors": 0}

    def never(*_):
        raise AssertionError("traced")

    booking = {"source": "traced"}
    second = _store(tmp_path, package).executable(
        op, shape, jax.jit(never), args, booking)
    assert _same(second(*args), want)
    assert jax.tree_util.tree_structure(second(*args)) == \
        jax.tree_util.tree_structure(want)
    assert _delta(before) == {"hits": 1, "misses": 1, "errors": 0}
    assert booking["source"] == "store" and booking["load_s"] > 0


@pytest.mark.parametrize("codec", ["zstd", "zlib"])
def test_a_file_is_packed_and_says_how(tmp_path, package, codec,
                                       monkeypatch):
    """A TPU executable of a pairing kernel is 250 MB that packs to an
    eighth; where `zstandard` is not installed, zlib does it."""
    if codec == "zlib":
        monkeypatch.setattr(execstore, "zstandard", None)
    op, shape, body, make_args = PROGRAMS["pytree_out"]
    args = make_args()
    store = _store(tmp_path, package)
    store.executable(op, shape, jax.jit(body), args)
    held = pickle.loads(Path(store.path(op, shape, args)).read_bytes())
    assert held["codec"] == codec
    assert len(held["payload"]) < len(
        execstore.decompress(codec, held["payload"]))
    monkeypatch.undo()   # a reader with zstandard reads either
    loaded, _ = _store(tmp_path, package).load(op, shape, args)
    assert _same(loaded(*args), body(*args))


def test_the_timers_book_one_load_and_one_store(tmp_path, package):
    op, shape, body, make_args = PROGRAMS["pytree_out"]
    load, store = (metrics.timer(f"jax/exec_store/{name}_time")
                   for name in ("load", "store"))
    n_load, n_store = load.count, store.count
    for _ in range(2):
        _store(tmp_path, package).executable(op, shape, jax.jit(body),
                                             make_args())
    # the miss timed no load, the hit no store
    assert (load.count, store.count) == (n_load + 1, n_store + 1)


# == the key ================================================================


def _digest_with(tmp_path, package, what):
    environ, changes = {}, {}
    if what == "source_byte":
        path = package / "ops" / "kernel.py"
        path.write_bytes(path.read_bytes().replace(b"x\n", b"y\n"))
    elif what == "source_file_added":
        (package / "ops" / "more.py").write_bytes(b"")
    elif what == "source_file_renamed":
        (package / "ops" / "kernel.py").rename(package / "ops" / "k.py")
    elif what == "package_variable":
        environ["GETHSHARDING_TPU_WIRE"] = "u16"
    elif what == "compiler_variable":
        environ["XLA_FLAGS"] = "--xla_dump_to=/nowhere"
    elif what == "other_variable":
        environ["BENCH_RUN"] = "7"
    elif what == "not_python":
        (package / "ops" / "notes.txt").write_bytes(b"x")
    else:
        changes[what] = "other" if what != "device_count" else 4
    return _store(tmp_path, package, environ=environ, **changes).digest


@pytest.mark.parametrize("what", [
    "source_byte", "source_file_added", "source_file_renamed",
    "package_variable", "compiler_variable", "jax", "jaxlib",
    "platform_version", "platform", "device_kind", "device_count"])
def test_the_digest_changes_with(tmp_path, package, what):
    plain = _store(tmp_path, package).digest
    assert len(plain) == 16
    assert _digest_with(tmp_path, package, what) != plain


@pytest.mark.parametrize("what", ["other_variable", "not_python"])
def test_the_digest_does_not_change_with(tmp_path, package, what):
    plain = _store(tmp_path, package).digest
    assert _digest_with(tmp_path, package, what) == plain


def test_this_process_keys_on_its_own_toolchain_and_package(tmp_path):
    store = ExecutableStore(str(tmp_path))
    assert store.key["toolchain"] == {
        "jax": jax.__version__, "jaxlib": __import__("jaxlib").__version__,
        "platform_version": jax.devices()[0].client.platform_version,
        "platform": "cpu", "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices())}
    assert store.key["source"] == execstore.source_digest(
        REPO / "gethsharding_tpu")
    assert all(name.startswith("GETHSHARDING_")
               or name in execstore.COMPILER_ENV
               for name in store.key["environ"])
    assert set(store.key["environ"]) >= {
        name for name in os.environ if name.startswith("GETHSHARDING_")}


def _args_with(what):
    x = jnp.zeros((4, 3), jnp.int32)
    return {
        "plain": (x, x),
        "shape": (jnp.zeros((4, 4), jnp.int32), x),
        "dtype": (x.astype(jnp.uint16), x),
        "weak_type": (x, jnp.asarray(1)),
        "strong_scalar": (x, jnp.asarray(1, jnp.int32)),
        "tree": ([x], x),
        "host_array": (np.zeros((4, 3), np.int32), x),
    }[what]


@pytest.mark.parametrize("what", ["shape", "dtype", "tree"])
def test_the_file_name_changes_with_an_arguments(tmp_path, package, what):
    store = _store(tmp_path, package)
    assert store.path("op", (4,), _args_with(what)) != \
        store.path("op", (4,), _args_with("plain"))


def test_the_file_name_tells_a_weak_type_from_a_strong_one(tmp_path,
                                                           package):
    store = _store(tmp_path, package)
    assert store.path("op", (4,), _args_with("weak_type")) != \
        store.path("op", (4,), _args_with("strong_scalar"))


def test_the_file_name_is_the_op_the_shape_and_the_arguments(tmp_path,
                                                             package):
    store = _store(tmp_path, package)
    path = Path(store.path("bls_committee", (112, 144, "i32"),
                           _args_with("plain")))
    assert path.parent == tmp_path / "executables" / store.digest
    assert path.name.startswith("bls_committee-112x144xi32-")
    assert path.suffix == ".exe" and len(path.stem.rsplit("-", 1)[1]) == 12
    # where the bytes lie does not matter, what they are does
    assert str(path) == store.path("bls_committee", (112, 144, "i32"),
                                   _args_with("host_array"))
    assert str(path) != store.path("bls_committee", (56, 144, "i32"),
                                   _args_with("plain"))
    assert str(path) != store.path("bls", (112, 144, "i32"),
                                   _args_with("plain"))


# == a load that fails costs a trace, never a request =======================


def _spoil(path: Path, how: str, tmp_path, package) -> None:
    if how == "truncated":
        path.write_bytes(path.read_bytes()[:-100])
    elif how == "garbage":
        path.write_bytes(os.urandom(4096))
    elif how == "empty":
        path.write_bytes(b"")
    elif how == "foreign":
        # a whole file, of another key, under this name
        body = pickle.loads(path.read_bytes())
        body["key"] = _store(tmp_path, package, jax="0.0.1").key
        path.write_bytes(pickle.dumps(body))
    elif how == "other_arguments":
        body = pickle.loads(path.read_bytes())
        body["args"] += ";int32[1]"
        path.write_bytes(pickle.dumps(body))
    elif how == "unknown_codec":
        body = pickle.loads(path.read_bytes())
        body["codec"] = "lzma"
        path.write_bytes(pickle.dumps(body))
    elif how == "no_payload":
        body = pickle.loads(path.read_bytes())
        body["payload"] = body["payload"][:64]
        path.write_bytes(pickle.dumps(body))


@pytest.mark.parametrize("how", ["truncated", "garbage", "empty", "foreign",
                                 "other_arguments", "unknown_codec",
                                 "no_payload"])
def test_a_spoiled_file_is_counted_removed_and_the_call_answers(
        tmp_path, package, how, caplog):
    op, shape, body, make_args = PROGRAMS["pytree_out"]
    fn, args = jax.jit(body), make_args()
    store = _store(tmp_path, package)
    store.executable(op, shape, fn, args)
    path = Path(store.path(op, shape, args))
    _spoil(path, how, tmp_path, package)
    before = _counts()
    assert _store(tmp_path, package).load(op, shape, args) is None
    assert _delta(before) == {"hits": 0, "misses": 0, "errors": 1}
    assert not path.exists()
    assert "could not load" in caplog.text
    # through the whole path: the error costs a trace, and the file that
    # the trace leaves is whole again
    _spoil_again = _store(tmp_path, package)
    _spoil_again.executable(op, shape, fn, args)
    _spoil(path, how, tmp_path, package)
    before = _counts()
    booking = {"source": "traced"}
    exe = _store(tmp_path, package).executable(op, shape, fn, args, booking)
    assert _same(exe(*args), body(*args))
    assert booking == {"source": "traced"}
    assert _delta(before) == {"hits": 0, "misses": 0, "errors": 1}
    assert _store(tmp_path, package).load(op, shape, args) is not None


def test_a_store_that_cannot_write_still_hands_back_the_executable(
        tmp_path, package, caplog):
    op, shape, body, make_args = PROGRAMS["pytree_out"]
    args = make_args()
    (tmp_path / "executables").write_bytes(b"a file where the root should be")
    before = _counts()
    store = _store(tmp_path, package)
    store._opened = True   # the directory cannot even be made
    exe = store.trace(op, shape, jax.jit(body), args)
    assert _same(exe(*args), body(*args))
    assert _delta(before)["errors"] == 1
    assert "could not store" in caplog.text


# == writers, directories ===================================================


def test_two_writers_of_one_key_leave_one_whole_file(tmp_path, package):
    op, shape, body, make_args = PROGRAMS["pytree_out"]
    args = make_args()
    exe = jax.jit(body).lower(*args).compile()
    stores = [_store(tmp_path, package) for _ in range(4)]
    gate = threading.Barrier(len(stores))

    def write(store):
        gate.wait()
        for _ in range(5):
            store.save(op, shape, args, exe)

    threads = [threading.Thread(target=write, args=(s,)) for s in stores]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join()
    files = sorted(p.name for p in Path(stores[0].dir).iterdir())
    assert files == [Path(stores[0].path(op, shape, args)).name]
    loaded, _ = _store(tmp_path, package).load(op, shape, args)
    assert _same(loaded(*args), body(*args))


@pytest.mark.parametrize("others, removed", [(2, 0), (3, 1), (5, 3)])
def test_a_new_digests_directory_keeps_the_two_most_recently_used_others(
        tmp_path, package, others, removed):
    op, shape, body, make_args = PROGRAMS["list_args"]
    root = tmp_path / "executables"
    older = []
    for age in range(others):
        store = _store(tmp_path, package, platform_version=f"old{age}")
        store.executable(op, shape, jax.jit(body), make_args())
        # used longest ago first: age 0 is the oldest
        os.utime(store.dir, (1_000_000 + age, 1_000_000 + age))
        older.append(store.digest)
    _store(tmp_path, package).executable(op, shape, jax.jit(body),
                                         make_args())
    left = {p.name for p in root.iterdir()}
    assert left == set(older[removed:]) | {_store(tmp_path, package).digest}
    # a directory that is there already removes nothing
    shutil.copytree(root / older[-1], root / "0123456789abcdef")
    _store(tmp_path, package).executable(op, shape, jax.jit(body),
                                         make_args())
    assert len(list(root.iterdir())) == len(left) + 1


def test_using_a_directory_makes_it_recent(tmp_path, package):
    op, shape, body, make_args = PROGRAMS["list_args"]
    store = _store(tmp_path, package)
    store.executable(op, shape, jax.jit(body), make_args())
    os.utime(store.dir, (1_000_000, 1_000_000))
    _store(tmp_path, package).executable(op, shape, jax.jit(body),
                                         make_args())
    assert os.stat(store.dir).st_mtime > 2_000_000


# == a backend handed a store ===============================================


def _backend(tmp_path):
    """A backend on the store, booking on a watch of its own: the
    process's storm window is other tests' to fill."""
    from gethsharding_tpu.devscope import CompileWatch
    from gethsharding_tpu.sigbackend import JaxSigBackend

    backend = JaxSigBackend(
        exec_store=ExecutableStore(str(tmp_path / "exe")))
    backend._compiles = CompileWatch(registry=metrics.Registry())
    return backend


def test_a_backend_launches_through_what_it_holds(tmp_path):
    body = PROGRAMS["list_args"][2]
    args = _stack_args()
    first = _backend(tmp_path)
    before = _counts()
    out = first._counted_launch("line_table_stack", (3,), jax.jit(body),
                                *args)
    assert _same(out, body(*args))
    held = first._held[("line_table_stack", 3)]
    assert isinstance(held, jax.stages.Compiled)
    assert _delta(before) == {"hits": 0, "misses": 1, "errors": 0}

    def never(*_):
        raise AssertionError("traced")

    # the shape is held: nothing asks the store, nothing calls `fn`
    out = first._counted_launch("line_table_stack", (3,), jax.jit(never),
                                *args)
    assert _same(out, body(*args))
    assert _delta(before) == {"hits": 0, "misses": 1, "errors": 0}
    # a second backend (a restarted server) loads by shape
    second = _backend(tmp_path)
    out = second._counted_launch("line_table_stack", (3,), jax.jit(never),
                                 *args)
    assert _same(out, body(*args))
    assert _delta(before) == {"hits": 1, "misses": 1, "errors": 0}
    # another shape of the same op is another program
    out = second._counted_launch("line_table_stack", (2,), jax.jit(body),
                                 *_stack_args(2))
    assert out[0].shape == (2, 5, 2)
    assert _delta(before) == {"hits": 1, "misses": 2, "errors": 0}


def test_a_loaded_executable_that_refuses_its_first_call_costs_a_trace(
        tmp_path, caplog):
    body = PROGRAMS["list_args"][2]
    args = _stack_args()
    _backend(tmp_path)._counted_launch("line_table_stack", (3,),
                                       jax.jit(body), *args)
    backend = _backend(tmp_path)
    store = backend._exec_store
    path = Path(store.path("line_table_stack", (3,), args))

    def refusing(*_):
        raise RuntimeError("refused")

    store.load = lambda op, shape, args: (refusing, 0.25)
    before = _counts()
    out = backend._counted_launch("line_table_stack", (3,), jax.jit(body),
                                  *args)
    assert _same(out, body(*args))
    assert _delta(before)["errors"] == 1
    assert "refused its first call" in caplog.text
    assert isinstance(backend._held[("line_table_stack", 3)],
                      jax.stages.Compiled)
    # the file the trace left is whole
    del store.load
    assert path.exists() and store.load("line_table_stack", (3,), args)


def test_a_traced_executable_that_raises_is_not_retried(tmp_path):
    backend = _backend(tmp_path)
    with pytest.raises(TypeError):
        # compiled for three rows, called with what cannot be lowered
        backend._counted_launch("line_table_stack", (3,),
                                jax.jit(PROGRAMS["list_args"][2]),
                                object(), object())
    assert ("line_table_stack", 3) not in backend._held


_RESTART = """
import sys, tempfile
import jax
from gethsharding_tpu import devscope, metrics
from gethsharding_tpu.crypto import bn256 as bls
from gethsharding_tpu.ops import bn256_jax
from gethsharding_tpu.sigbackend import JaxSigBackend, get_backend
from gethsharding_tpu.sigbackend.execstore import ExecutableStore

root = sys.argv[1]
keys = [bls.bls_keygen(b"restart-%d" % i) for i in range(2)]
msgs = [b"header-0", b"header-1"]
sig_rows = [[bls.bls_sign(m, sk) for sk, _ in keys] for m in msgs]
sig_rows[1][0] = bls.bls_sign(b"forged", keys[0][0])
pk_rows = [[pk for _, pk in keys]] * 2
want = get_backend("python").bls_verify_committees(msgs, sig_rows, pk_rows)
assert want == [True, False], want


def count(name):
    return metrics.counter("jax/exec_store/" + name).value


first = JaxSigBackend(exec_store=ExecutableStore(root))
assert first.bls_verify_committees(msgs, sig_rows, pk_rows) == want
assert (count("hits"), count("misses"), count("errors")) == (0, 1, 0)
# a restarted server: nothing of the first backend's is left, and the
# program cannot be traced again
del first
jax.clear_caches()


def never(*args, **kwargs):
    raise AssertionError("traced")


bn256_jax.bls_aggregate_verify_committee_batch = never
second = JaxSigBackend(exec_store=ExecutableStore(root))
assert second.bls_verify_committees(msgs, sig_rows, pk_rows) == want
assert (count("hits"), count("misses"), count("errors")) == (1, 1, 0)
top = [s for s in devscope.COMPILES.describe()["top_shapes"]
       if s["op"] == "bls_committee"]
assert top[0]["source"] == "store" and top[0]["load_s"] > 0, top
# and without the store the patched kernel does raise: the proof proves
third = JaxSigBackend()
assert third._exec_store is None
try:
    third.bls_verify_committees(msgs, sig_rows, pk_rows)
except AssertionError as exc:
    assert "traced" in str(exc)
else:
    raise SystemExit("the patched kernel was not reached")
print("RESTART-OK")
"""


def test_a_restarted_backend_verifies_a_committee_and_traces_nothing(
        tmp_path):
    """The pairing kernel itself, in a child with a compile cache of its
    own: XLA:CPU cannot serialize again an executable that it read from
    the compile cache (the copy lacks its kernels' functions and fails
    at the first pull), so the child compiles cold. A TPU's executable
    survives that (PERF.md, PR 38, step 0)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax"),
               PYTHONPATH=str(REPO) + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    done = subprocess.run(
        [sys.executable, "-c", _RESTART, str(tmp_path / "exe")], env=env,
        cwd=str(tmp_path), capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    assert "RESTART-OK" in done.stdout
