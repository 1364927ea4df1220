"""The executable store: compiled programs on disk, found by shape.

A process that has never launched an (op, shape) pays for it twice over
under `jax.jit`: Python traces the program and lowers it (the pairing
graph is some 80,000 lines of lowered text: 24-27 s), and only then
does JAX hash the module and read the compiled executable from its
persistent cache. A restarted server has every program it needs on
disk and still recomputes the names under which they lie.

`ExecutableStore` keeps the compiled executable itself
(`jax.experimental.serialize_executable`, compressed as JAX's own
cache compresses) under a name that needs no trace to compute: the op,
the arguments' shapes, dtypes and weak-type flags, and one digest of
everything else that decides what a program compiles to. A fresh (op,
shape) asks here first. A hit is a read and a load (the runtime's own
deserialization: about 10 s for a pairing kernel's 253 MB; see
`ops/device.keep_freed_memory` for what it cost on a dispatch thread);
a miss is `fn.lower(*args).compile()`, as `jit`'s own first call does,
then a write. Either way the backend holds the executable by (op,
shape) and calls it from then on (`dispatch.py` `_run`).

**The key can never serve a stale program.** The digest names the
directory and is taken over: `jax`'s and `jaxlib`'s versions and the
backend's `platform_version` (the libtpu build); the platform,
`device_kind` and device count; every ``GETHSHARDING_*`` variable of
the process's environment, with ``XLA_FLAGS`` and ``LIBTPU_INIT_ARGS``;
and a SHA-256 over the bytes of every ``*.py`` under the package. Any
edit anywhere in the package re-traces, as it does today. A file also
carries its own key and is refused when that differs from the key it
was asked under.

    <compile cache>/executables/<digest16>/<op>-<shape>-<args12>.exe

**A load that fails costs a trace, never a request.** Whatever reading,
unpickling or loading raises is logged and counted, the file is
removed, and the program is traced and compiled as on a miss. A file is
written under another name and renamed, so the replicas of one host
share a directory. When a process makes a digest's directory, all but
the two most recently used others are removed: a developer's edits do
not grow the store without bound. Deleting ``executables/`` is always
safe. A file is unpickled: the directory is as trusted as the compile
cache beside it.

Where it engages is observed, not configured (`for_device`): wherever
the device record's platform is not ``cpu``. On the CPU a rehearsal
shape traces in seconds, and XLA:CPU cannot serialize again an
executable that it read from the compile cache (the copy lacks its
kernels' functions and fails at its first pull; a TPU's survives it).
Tests construct a store on a temporary directory and hand it to a
backend.

Registry: ``jax/exec_store/{hits,misses,errors}`` (counters),
``jax/exec_store/{load_time,store_time}`` (timers, each a
`tracing.stage`, so a traced set-up shows them as spans under the
dispatch that paid).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import shutil
import uuid
import zlib
from pathlib import Path
from typing import Optional

try:  # what JAX's own cache compresses with, where it is installed
    import zstandard
except ImportError:  # pragma: no cover - the installation has it
    zstandard = None

from gethsharding_tpu import metrics, tracing

log = logging.getLogger("sigbackend.execstore")

PACKAGE_DIR = Path(__file__).resolve().parents[1]
# beside GETHSHARDING_*: the two variables that reach the compiler
COMPILER_ENV = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")
KEEP_OTHER_DIGESTS = 2
SUFFIX = ".exe"

_M_HITS = metrics.counter("jax/exec_store/hits")
_M_MISSES = metrics.counter("jax/exec_store/misses")
_M_ERRORS = metrics.counter("jax/exec_store/errors")
_T_LOAD = metrics.timer("jax/exec_store/load_time")
_T_STORE = metrics.timer("jax/exec_store/store_time")


def source_digest(package_dir=PACKAGE_DIR) -> str:
    """SHA-256 over every ``*.py`` under `package_dir`: each file's
    path, length and bytes, in sorted order. Milliseconds, once a
    process."""
    root = Path(package_dir)
    digest = hashlib.sha256()
    for path in sorted(root.rglob("*.py")):
        body = path.read_bytes()
        digest.update(f"{path.relative_to(root).as_posix()}\0"
                      f"{len(body)}\0".encode())
        digest.update(body)
    return digest.hexdigest()


def toolchain_record() -> dict:
    """What compiles and what it compiles for, as JAX reports them
    (initializes the backend: call only in a process that has opted
    into the accelerator plane)."""
    import jax
    import jaxlib

    devices = jax.devices()
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "platform_version": devices[0].client.platform_version,
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def compress(payload: bytes) -> tuple:
    """``(codec, bytes)``: a TPU executable of a pairing kernel is some
    250 MB of instructions that pack to an eighth (PERF.md, PR 38)."""
    if zstandard is not None:
        return "zstd", zstandard.ZstdCompressor().compress(payload)
    return "zlib", zlib.compress(payload, 1)


def decompress(codec: str, packed: bytes) -> bytes:
    if codec == "zstd":
        return zstandard.ZstdDecompressor().decompress(packed)
    if codec == "zlib":
        return zlib.decompress(packed)
    raise ValueError(f"unknown codec {codec!r}")


def args_signature(args) -> str:
    """The pytree of `args` and every leaf's dtype, shape and weak-type
    flag: what `jit` would key its own cache on."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    parts = [str(treedef)]
    for leaf in leaves:
        aval = jax.typeof(leaf)
        parts.append(f"{aval.dtype}{list(aval.shape)}"
                     + ("w" if getattr(aval, "weak_type", False) else ""))
    return ";".join(parts)


class ExecutableStore:
    """One digest's directory of serialized executables (module
    docstring). `toolchain`, `environ` and `package_dir` are what the
    key is taken over; left out, they are this process's own."""

    def __init__(self, root: str, *, toolchain: Optional[dict] = None,
                 environ=None, package_dir=PACKAGE_DIR):
        environ = os.environ if environ is None else environ
        self.key = {
            "toolchain": dict(toolchain_record() if toolchain is None
                              else toolchain),
            "environ": {k: environ[k] for k in sorted(environ)
                        if k.startswith("GETHSHARDING_")
                        or k in COMPILER_ENV},
            "source": source_digest(package_dir),
        }
        self.digest = hashlib.sha256(json.dumps(
            self.key, sort_keys=True).encode()).hexdigest()[:16]
        self.root = str(root)
        self.dir = os.path.join(self.root, self.digest)
        self._opened = False

    @classmethod
    def for_device(cls, record: dict) -> Optional["ExecutableStore"]:
        """The process's store beside its compile cache, or None where
        the device record's platform is the CPU."""
        if record["platform"] == "cpu":
            return None
        from gethsharding_tpu.ops import device

        return cls(device.executable_store_dir())

    # -- the directory -----------------------------------------------------

    def _open(self) -> None:
        """Make or touch this digest's directory, once a process; a
        directory that had to be made removes all but the
        `KEEP_OTHER_DIGESTS` most recently used others."""
        if self._opened:
            return
        made = not os.path.isdir(self.dir)
        os.makedirs(self.dir, exist_ok=True)
        os.utime(self.dir)  # "used": what the next pruning sorts by
        self._opened = True
        if not made:
            return
        others = []
        for entry in os.scandir(self.root):
            if entry.is_dir() and entry.name != self.digest:
                others.append((entry.stat().st_mtime, entry.path))
        others.sort(reverse=True)
        for _, path in others[KEEP_OTHER_DIGESTS:]:
            shutil.rmtree(path, ignore_errors=True)

    def path(self, op: str, shape: tuple, args) -> str:
        return self._path(op, shape, args_signature(args))

    def _path(self, op: str, shape: tuple, signature: str) -> str:
        tail = hashlib.sha256(signature.encode()).hexdigest()
        name = "-".join([op, "x".join(str(s) for s in shape), tail[:12]])
        return os.path.join(self.dir, name + SUFFIX)

    # -- load, save --------------------------------------------------------

    def load(self, op: str, shape: tuple, args):
        """``(executable, seconds)``: the held form of (op, args), a
        `jax.stages.Compiled` loaded onto this process's first device,
        and what the load took. None where there is no file (a miss) or
        the file could not be read, unpickled or loaded (an error:
        logged, counted, removed)."""
        signature = args_signature(args)
        path = self._path(op, shape, signature)
        if not os.path.exists(path):
            _M_MISSES.inc()
            return None
        try:
            with tracing.stage("jax/exec_store/load_time", _T_LOAD) as load:
                exe = self._load(path, signature)
        except Exception:  # noqa: BLE001 - a trace, never a request
            _M_ERRORS.inc()
            log.exception("could not load %s: removed, the program is "
                          "traced", path)
            self.discard(path)
            return None
        _M_HITS.inc()
        return exe, load.seconds

    def _load(self, path: str, signature: str):
        import jax
        from jax.experimental import serialize_executable

        with open(path, "rb") as src:
            body = pickle.loads(src.read())
        if body["key"] != self.key or body["args"] != signature:
            raise ValueError("the file's key is not the key it lies under")
        return serialize_executable.deserialize_and_load(
            decompress(body["codec"], body["payload"]), body["in_tree"],
            body["out_tree"], execution_devices=jax.devices()[:1])

    def refused(self, op: str, shape: tuple, args) -> None:
        """A loaded executable raised at its first call: an error like
        a load that failed, and the file goes."""
        _M_ERRORS.inc()
        path = self.path(op, shape, args)
        log.exception("the executable loaded from %s refused its first "
                      "call: removed, the program is traced", path)
        self.discard(path)

    def discard(self, path: str) -> None:
        try:
            os.remove(path)
        except OSError:
            pass

    def save(self, op: str, shape: tuple, args, compiled) -> None:
        """Serialize `compiled` under (op, args): written under another
        name and renamed, so no reader sees half a file and two writers
        leave one whole one. What it raises is logged and counted: the
        caller holds its executable either way."""
        from jax.experimental import serialize_executable

        signature = args_signature(args)
        path = self._path(op, shape, signature)
        tmp = f"{path}.{os.getpid()}.{uuid.uuid4().hex[:8]}.part"
        try:
            self._open()
            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled)
            codec, payload = compress(payload)
            blob = pickle.dumps(
                {"key": self.key, "args": signature, "codec": codec, "payload": payload, "in_tree": in_tree,
                 "out_tree": out_tree}, protocol=pickle.HIGHEST_PROTOCOL)
            with open(tmp, "wb") as out:
                out.write(blob)
            os.replace(tmp, path)
        except Exception:  # noqa: BLE001 - the executable is held anyway
            _M_ERRORS.inc()
            log.exception("could not store %s", path)
            self.discard(tmp)

    # -- what a fresh (op, shape) asks -------------------------------------

    def executable(self, op: str, shape: tuple, fn, args, booking=None):
        """The executable of the jitted `fn` at `args`: loaded, or
        lowered and compiled exactly once and stored. `booking` is
        `compile_span`'s record of this compile: it learns the source
        and, on a hit, the load's seconds."""
        self._open()
        loaded = self.load(op, shape, args)
        if loaded is None:
            return self.trace(op, shape, fn, args)
        exe, load_s = loaded
        if booking is not None:
            booking.update(source="store", load_s=load_s)
        return exe

    def trace(self, op: str, shape: tuple, fn, args):
        """A miss's half of `executable`: trace, lower, compile (which
        reads JAX's compile cache), store."""
        exe = fn.lower(*args).compile()
        with tracing.stage("jax/exec_store/store_time", _T_STORE):
            self.save(op, shape, args, exe)
        return exe


__all__ = ["ExecutableStore", "args_signature", "source_digest",
           "toolchain_record"]
