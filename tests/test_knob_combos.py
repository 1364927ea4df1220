"""The autotune knob matrix must be sound COMBINED, not just per knob.

Each case runs the committee-verify kernel end to end (good + tampered
rows) in a subprocess with the knob env set — the knobs are read at
import, so a fresh interpreter is the only honest way to exercise a
configuration exactly as the bench's sweep children deploy it."""

import os
import subprocess
import sys

import pytest

slow = pytest.mark.skipif(
    os.environ.get("GETHSHARDING_SKIP_SLOW") == "1",
    reason="GETHSHARDING_SKIP_SLOW=1",
)

_DRIVER = """
from gethsharding_tpu.parallel.virtual import force_virtual_cpu_devices
force_virtual_cpu_devices(1)
import numpy as np, jax.numpy as jnp, jax
from gethsharding_tpu.crypto import bn256 as ref
from gethsharding_tpu.ops import bn256_jax as k

tag = b"combo-drive"
keys = [ref.bls_keygen(tag + bytes([j])) for j in range(3)]
sigs = [ref.bls_sign(tag, sk) for sk, _ in keys]
pks = [pk for _, pk in keys]
bad = [sigs[0], sigs[1], ref.g1_add(sigs[2], ref.G1_GEN)]
hx, hy, hok = k.g1_to_limbs([ref.hash_to_g1(tag)] * 2)
sx, sy, sm = k.g1_committee_to_limbs([sigs, bad], 3)
gx, gy, gm = k.g2_committee_to_limbs([pks, pks], 3)
out = jax.jit(k.bls_aggregate_verify_committee_batch)(
    jnp.asarray(hx), jnp.asarray(hy), jnp.asarray(sx), jnp.asarray(sy),
    jnp.asarray(sm), jnp.asarray(gx), jnp.asarray(gy), jnp.asarray(gm),
    jnp.asarray(hok))
assert [bool(v) for v in np.asarray(out)] == [True, False], out
print("combo-ok")
"""

COMBOS = [
    # the sweep's prime candidates
    {"GETHSHARDING_TPU_LIMB_FORM": "wide", "GETHSHARDING_TPU_NORM": "relaxed",
     "GETHSHARDING_TPU_PAIR_UNROLL": "finalexp"},
    # mega finalexp on CPU exercises the knob wiring + XLA fallback (the
    # kernel itself is interpret-tested in test_pallas_finalexp)
    {"GETHSHARDING_TPU_LIMB_FORM": "exact", "GETHSHARDING_TPU_CARRY": "scan",
     "GETHSHARDING_TPU_FINALEXP": "mega"},
    {"GETHSHARDING_TPU_LIMB_FORM": "exact", "GETHSHARDING_TPU_CARRY": "unroll",
     "GETHSHARDING_TPU_SCAN_UNROLL": "4"},
    {"GETHSHARDING_TPU_LIMB_FORM": "wide", "GETHSHARDING_TPU_NORM": "relaxed",
     "GETHSHARDING_TPU_SCAN_UNROLL": "4"},
]


_RELAXED_CANON_DRIVER = """
from gethsharding_tpu.parallel.virtual import force_virtual_cpu_devices
force_virtual_cpu_devices(1)
import numpy as np
from gethsharding_tpu.ops import limb
from gethsharding_tpu.ops.bn256_jax import FP

# a value < p in a QUASI-canonical representation (one -1 limb, value
# unchanged): canon must still emit the unique canonical limb vector,
# or eq/is_zero would report two equal field values unequal
v = FP.p - 12345
base = limb.int_to_limbs(v)
k = int(np.argmin(base[1:])) + 1  # a zero-ish limb to drive to -1
quasi = base.copy()
quasi[k] -= 1
quasi[k - 1] += 1 << limb.LIMB_BITS
got = np.asarray(FP.canon(quasi[None]))[0]
assert (got == base).all(), (got, base)
assert bool(FP.eq(quasi[None], base[None])[0])
print("canon-ok")
"""


def test_relaxed_canon_handles_quasi_canonical_limbs():
    env = {key: val for key, val in os.environ.items()
           if not key.startswith("GETHSHARDING_TPU_")}
    env.update({"GETHSHARDING_TPU_LIMB_FORM": "wide",
                "GETHSHARDING_TPU_NORM": "relaxed"})
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", _RELAXED_CANON_DRIVER],
                          env=env, capture_output=True, text=True,
                          timeout=600, cwd=repo_root)
    assert proc.returncode == 0 and "canon-ok" in proc.stdout, (
        proc.stdout[-500:], proc.stderr[-1500:])


def test_finalexp_mega_conflicts_with_pair_unroll():
    env = {key: val for key, val in os.environ.items()
           if not key.startswith("GETHSHARDING_TPU_")}
    env.update({"GETHSHARDING_TPU_FINALEXP": "mega",
                "GETHSHARDING_TPU_PAIR_UNROLL": "finalexp"})
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c",
         "from gethsharding_tpu.parallel.virtual import "
         "force_virtual_cpu_devices\n"
         "force_virtual_cpu_devices(1)\n"
         "import gethsharding_tpu.ops.bn256_jax\n"],
        env=env, capture_output=True, text=True, timeout=600, cwd=repo_root)
    assert proc.returncode != 0 and "FINALEXP" in proc.stderr


@slow
@pytest.mark.parametrize("combo", COMBOS,
                         ids=["relaxed+feunroll", "mega", "unroll+su4",
                              "relaxed+su4"])
def test_knob_combo_committee_verify(combo):
    # a clean knob slate: ambient GETHSHARDING_TPU_* exports must not
    # leak into the configuration under test
    env = {key: val for key, val in os.environ.items()
           if not key.startswith("GETHSHARDING_TPU_")}
    env.update(combo)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", _DRIVER], env=env,
                          capture_output=True, text=True, timeout=1500,
                          cwd=repo_root)
    assert proc.returncode == 0 and "combo-ok" in proc.stdout, (
        combo, proc.stdout[-500:], proc.stderr[-1500:])
