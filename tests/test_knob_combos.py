"""The 22-limb form, end to end.

Runs the committee-verify kernel end to end (good + tampered rows) in a
subprocess with the form set — it is read at import, so a fresh
interpreter is the only honest way to exercise the configuration as
`chip_smoke.py` leg C deploys it."""

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

# the CPU runs the XLA pairing under the 22-limb form (the Pallas
# kernels a chip chooses are interpret-tested in test_pallas_finalexp)
MEGA = {"GETHSHARDING_TPU_LIMB_FORM": "exact"}


@slow
def test_mega_switches_committee_verify():
    # a clean slate: ambient GETHSHARDING_TPU_* exports must not leak
    # into the configuration under test
    env = {key: val for key, val in os.environ.items()
           if not key.startswith("GETHSHARDING_TPU_")}
    env.update(MEGA)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", _DRIVER], env=env,
                          capture_output=True, text=True, timeout=1500,
                          cwd=repo_root)
    assert proc.returncode == 0 and "combo-ok" in proc.stdout, (
        proc.stdout[-500:], proc.stderr[-1500:])
