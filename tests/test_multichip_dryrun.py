"""The multi-chip dryrun's rc/tail contract.

``dryrun_multichip`` child output can carry an alarming-looking XLA:CPU
AOT loader error (``cpu_aot_loader.cc``: machine-feature mismatch,
"could lead to execution errors such as SIGILL") next to ``rc: 0`` —
benign in every observed run. These tests pin the contract down:

* the classifier in ``parallel.virtual`` recognizes exactly that noise
  class, and never excuses a nonzero rc;
* the dryrun child, run the same way the driver runs it (clean
  subprocess, forced virtual CPU platform), exits 0 with every stderr
  line either classified warn-only or ordinary log noise.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

from gethsharding_tpu.parallel.virtual import (
    assert_aot_warn_only,
    build_virtual_env,
    is_aot_mismatch_line,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Verbatim (truncated) lines of a recorded dryrun tail — the shape of
# the noise this classifier exists for.
AOT_LINES = (
    "E0802 02:06:29.925595   20031 cpu_aot_loader.cc:210] Loading "
    "XLA:CPU AOT result. Target machine feature +prefer-no-gather is "
    "not  supported on the host machine.",
    "Machine type used for XLA:CPU compilation doesn't match the host "
    "machine. This could lead to execution errors such as SIGILL.",
)

# Lines that must NOT be classified away (from the r01 failure tail and
# ordinary jax logging).
REAL_LINES = (
    "Traceback (most recent call last):",
    "ValueError: requested 8 devices, only 1 visible",
    "WARNING:2026-07-29 20:51:57,630:jax._src.xla_bridge:905: An NVIDIA "
    "GPU may be present on this machine, but a CUDA-enabled jaxlib is "
    "not installed. Falling back to cpu.",
)


def test_classifier_recognizes_aot_mismatch_lines():
    for line in AOT_LINES:
        assert is_aot_mismatch_line(line), line
    for line in REAL_LINES:
        assert not is_aot_mismatch_line(line), line


def test_warn_only_never_excuses_failure():
    tail = "\n".join(AOT_LINES)
    assert assert_aot_warn_only(0, tail) == list(AOT_LINES)
    assert assert_aot_warn_only(0, "") == []
    with pytest.raises(RuntimeError, match="warn-only"):
        assert_aot_warn_only(1, tail)
    with pytest.raises(RuntimeError):
        assert_aot_warn_only(-11, "")  # e.g. an actual SIGSEGV/SIGILL


@pytest.mark.slow
def test_dryrun_child_rc_and_tail():
    """Run the dryrun child exactly as the driver does — clean
    subprocess, virtual CPU platform forced via env — and assert the
    rc/tail contract instead of snapshotting it to JSON."""
    env = build_virtual_env(2)
    env["GETHSHARDING_DRYRUN_REEXEC"] = "1"
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(2)"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=840,
    )
    matched = assert_aot_warn_only(proc.returncode, proc.stderr)
    # Whatever stderr remains after the warn-only class must be ordinary
    # log noise (jax/absl WARNING|I|E-prefixed), never a traceback.
    leftovers = [ln for ln in proc.stderr.splitlines()
                 if ln.strip() and ln not in matched]
    for line in leftovers:
        assert "Traceback" not in line and "Error" not in line.split(
            ":", 1)[0], proc.stderr[-4000:]
