"""Which device the kernels run on, and where their compiled programs live.

Two process-wide facts every accelerated entry point needs, decided in
ONE place so no caller can drift from another:

- the device record: platform / device_kind / count as JAX reports them,
  resolved once, and a refusal to run on the CPU unless the operator
  asked for it. With ``JAX_PLATFORMS`` unset and no chip reachable (none
  on the host, or another process already holds it) JAX logs libtpu
  errors and quietly hands back ``[CpuDevice(id=0)]``; an accelerated
  backend that accepted that would serve verdicts from the CPU under the
  device's name. `device_record` raises instead.
- the persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR`` is
  set JAX already keeps its cache there and this module sets no other
  directory; where it is not, every process of the program uses the one
  fixed ``<checkout>/.jax_cache``. Entries are found by path, so the
  directory never depends on the host, the pid or the time. Inside it,
  ``executables/`` holds the programs themselves, found by shape with
  nothing traced (`sigbackend/execstore.py`).

- the allocator: a process that holds a TPU has some 180 threads, and a
  thread that is not the main one allocates from an arena of glibc's
  that hands memory back to the kernel at every large `free` and faults
  it in again at the next `malloc`. Deserializing a pairing kernel's
  253 MB executable (a compile-cache read, a load from the executable
  store) on a dispatch thread took 65-66 s that way and 12-13 s on the
  main thread or with the arenas told to keep what is freed (PERF.md,
  PR 38). `device_record` tells them, off the CPU.

JAX stays a lazy import: control planes import this module freely.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
PLATFORMS_ENV = "JAX_PLATFORMS"


class NoAcceleratorError(RuntimeError):
    """JAX resolved to the CPU and the operator did not ask for the CPU."""


def compile_cache_dir() -> str:
    """The compile-cache directory in force for this process."""
    return os.environ.get(CACHE_ENV) or str(
        Path(__file__).resolve().parents[2] / ".jax_cache")


def executable_store_dir() -> str:
    """Where the serialized executables lie (`sigbackend/execstore.py`):
    ``executables/`` inside the compile cache's directory, so that what
    keeps the one keeps the other."""
    return os.path.join(compile_cache_dir(), "executables")


def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache at `compile_cache_dir()` and
    return it. With the environment variable set this writes no
    directory at all (JAX read the variable itself at import); the
    thresholds keep every multi-second kernel compile and skip the
    sub-2-second glue programs."""
    import jax

    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


def cpu_declared() -> bool:
    """True when the operator named the CPU platform: ``JAX_PLATFORMS``
    (or the ``jax_platforms`` config the virtual-device forcing writes)
    lists ``cpu``. Tests and the smoke rehearsal declare it."""
    import jax

    named = ",".join(filter(None, (os.environ.get(PLATFORMS_ENV, ""),
                                   jax.config.jax_platforms or "")))
    return "cpu" in [p.strip().lower() for p in named.split(",")]


# mallopt(3)'s parameters and the values the chip run was made with:
# nothing below 1 GiB is mmapped by itself, no heap is trimmed or given
# up, an arena grows in steps of 256 MiB
_MALLOPT = ((-3, 1 << 30),         # M_MMAP_THRESHOLD
            (-1, (1 << 31) - 1),   # M_TRIM_THRESHOLD
            (-2, 1 << 28))         # M_TOP_PAD


def keep_freed_memory() -> bool:
    """Tell glibc's allocator to keep what the process frees, in every
    thread's arena (module docstring). The process's resident size then
    stays at its high-water mark, which a (de)serialized executable
    sets: about 1 GiB. False where the C library has no `mallopt`."""
    import ctypes

    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    mallopt.argtypes = (ctypes.c_int, ctypes.c_int)
    mallopt.restype = ctypes.c_int
    # a list, so that one refusal does not keep the others from being set
    return all([mallopt(param, value) == 1 for param, value in _MALLOPT])


_resolved: Optional[dict] = None


def device_record() -> dict:
    """``{"platform", "device_kind", "count", "compile_cache_dir"}``:
    this process's JAX devices as JAX reports them, resolved once (the
    first call places the compile cache, initializes the backend and, on
    a TPU host, takes every chip libtpu can see). THE call by which a
    process opts into the accelerator plane, whatever its entry point.

    Raises `NoAcceleratorError` when the platform is ``cpu`` and the CPU
    was not declared — the no-silent-CPU rule."""
    global _resolved
    if _resolved is None:
        import jax

        cache_dir = configure_compile_cache()
        devices = jax.devices()
        record = {"platform": devices[0].platform,
                  "device_kind": devices[0].device_kind,
                  "count": len(devices),
                  "compile_cache_dir": cache_dir}
        if record["platform"] == "cpu" and not cpu_declared():
            raise NoAcceleratorError(
                "JAX found no accelerator and fell back to the CPU "
                f"({record['count']} x {record['device_kind']}): no TPU on "
                "this host, or another process already holds the chip "
                "(one jax process per host today; libtpu gives the first "
                "process every chip). Set JAX_PLATFORMS=cpu to run on the "
                "CPU on purpose.")
        if record["platform"] != "cpu":
            keep_freed_memory()
        _resolved = record
    return _resolved


def resolved_record() -> Optional[dict]:
    """The device record IF this process already resolved its devices,
    else None — for observers (the devscope poller) that must never be
    the thing that initializes a backend: a control-plane process with
    the scalar backend must not take the chip just to report on it."""
    return _resolved
