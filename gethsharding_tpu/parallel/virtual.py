"""Virtual-device forcing: validate multi-chip layouts without real chips.

Multi-chip shardings are validated on XLA's host-platform virtual CPU
devices (``--xla_force_host_platform_device_count``). This is the single
shared implementation used by both ``tests/conftest.py`` and
``__graft_entry__.dryrun_multichip`` so the two cannot drift.

Forcing must happen before the first XLA client is created in the
process: XLA parses the flag once.
"""

from __future__ import annotations

import os
import re

_COUNT_RE = re.compile(r"--xla_force_host_platform_device_count=(\d+)")

# The ONE known-benign stderr class of a virtual-mesh dryrun child:
# XLA:CPU's AOT loader logs E-severity machine-feature mismatch lines
# (cpu_aot_loader.cc) when it loads a persistent-cache executable
# ("Target machine feature +prefer-no-gather is not supported ... could
# lead to execution errors such as SIGILL"). They appear with rc=0 and
# bit-identical outputs, also on the very machine that compiled the
# entry, so the lines are WARN-ONLY — they must never fail a dryrun,
# and they must never excuse a real failure (rc != 0 fails regardless
# of what the tail says).
AOT_MISMATCH_MARKERS = (
    "cpu_aot_loader",
    "machine type used for xla:cpu compilation doesn't match",
    "target machine feature",
    "could lead to execution errors such as sigill",
)


def is_aot_mismatch_line(line: str) -> bool:
    """True when a stderr line belongs to the XLA:CPU AOT
    machine-feature mismatch class (see `AOT_MISMATCH_MARKERS`)."""
    low = line.lower()
    return any(marker in low for marker in AOT_MISMATCH_MARKERS)


def assert_aot_warn_only(rc: int, tail: str):
    """The dryrun child verdict: rc decides, the AOT mismatch lines in
    the captured tail are classified as warn-only noise. Returns the
    matched lines on success; raises ``RuntimeError`` on rc != 0 —
    explicitly even when mismatch lines are present, so the benign
    class can never mask a real crash (e.g. an actual SIGILL exits
    nonzero and fails here with the tail attached)."""
    matched = [line for line in tail.splitlines()
               if is_aot_mismatch_line(line)]
    if rc != 0:
        raise RuntimeError(
            f"virtual-mesh dryrun child failed (rc={rc}); the AOT "
            f"machine-feature mismatch warning is warn-only and never "
            f"excuses a failure. stderr tail:\n{tail[-4000:]}")
    return matched


def requested_virtual_cpu_count() -> int:
    """Virtual CPU device count currently requested via XLA_FLAGS (0 if none)."""
    m = _COUNT_RE.search(os.environ.get("XLA_FLAGS", ""))
    return int(m.group(1)) if m else 0


def build_virtual_env(n: int, base_env=None) -> dict:
    """A copy of ``base_env`` (default: os.environ) with the virtual CPU
    platform forced for a CHILD process: JAX_PLATFORMS=cpu and the
    host-platform device-count flag rewritten to ``n``."""
    env = dict(os.environ if base_env is None else base_env)
    env["JAX_PLATFORMS"] = "cpu"
    flags = _COUNT_RE.sub("", env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}"
    ).strip()
    return env


def force_virtual_cpu_devices(n: int) -> None:
    """Force >= ``n`` visible JAX devices via the virtual CPU host platform.

    Idempotent; safe to call again in a process where it already ran (e.g.
    under pytest where conftest ran it at collection time). Must run before
    the first backend init to have any effect on the device count.

    Also configures the persistent compile cache (`ops.device`): the
    pairing kernels take minutes to compile cold on XLA:CPU; cache hits
    make repeat runs take seconds.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if requested_virtual_cpu_count() < n:
        flags = _COUNT_RE.sub("", flags)
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")

    from gethsharding_tpu.ops.device import configure_compile_cache

    configure_compile_cache()
