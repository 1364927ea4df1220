"""The device rule, the cache rule and the chip smoke's rehearsal.

- no silent CPU: an accelerated backend resolves its devices once,
  records platform / device_kind / count, and refuses a CPU that
  ``JAX_PLATFORMS`` did not name; the chain_server banner and
  ``shard_health`` carry the record; a requested Pallas knob never gets
  the XLA path in its place off the CPU;
- one compile cache: ``JAX_COMPILATION_CACHE_DIR`` where set, else the
  fixed ``<checkout>/.jax_cache``, for every entry point;
- ``chip_smoke.py --rehearsal`` passes on the CPU and the same command
  without the flag fails off the chip.
"""

import json
import os
import subprocess
import sys

import pytest

from gethsharding_tpu.ops import device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code, env_update, timeout=180):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR",
                        "XLA_FLAGS")}
    env.update(env_update)
    env["PYTHONPATH"] = REPO
    return subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


# == the device rule ========================================================


def test_undeclared_cpu_is_refused_naming_jax_platforms():
    """With JAX_PLATFORMS unset and no chip JAX quietly hands back the
    CPU; `JaxSigBackend()` must raise, and say how to ask for the CPU."""
    proc = _run("from gethsharding_tpu.sigbackend.dispatch import "
                "JaxSigBackend; JaxSigBackend()", {})
    assert proc.returncode != 0
    assert "NoAcceleratorError" in proc.stderr
    assert "JAX_PLATFORMS=cpu" in proc.stderr


def test_declared_cpu_constructs_and_exposes_the_record():
    from gethsharding_tpu.sigbackend import device_record_of, get_backend
    from gethsharding_tpu.serving import ServingSigBackend

    backend = get_backend("jax")
    record = backend.device_record
    assert record["platform"] == "cpu"
    assert record["device_kind"] and record["count"] == 8  # conftest's mesh
    assert record["compile_cache_dir"] == device.compile_cache_dir()
    # found through the wrapper chain; absent under a scalar composition
    serving = ServingSigBackend(backend)
    try:
        assert device_record_of(serving) == record
    finally:
        serving.close()
    assert device_record_of(get_backend("python")) is None


def test_cpu_declared_reads_env_and_config(monkeypatch):
    import jax

    assert device.cpu_declared()  # conftest forced the cpu platform
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setattr(type(jax.config), "jax_platforms",
                        property(lambda self: "tpu,cpu"), raising=False)
    assert device.cpu_declared()
    monkeypatch.setattr(type(jax.config), "jax_platforms",
                        property(lambda self: None), raising=False)
    assert not device.cpu_declared()
    monkeypatch.setenv("JAX_PLATFORMS", "CPU")
    assert device.cpu_declared()


def test_observer_device_replay_obeys_the_rule(monkeypatch):
    from gethsharding_tpu.actors.observer import Observer

    monkeypatch.setattr(device, "_resolved", None)
    monkeypatch.setattr(device, "cpu_declared", lambda: False)
    with pytest.raises(device.NoAcceleratorError, match="JAX_PLATFORMS"):
        Observer(client=None, shard=None, replay_engine="jax")


def test_health_and_banner_carry_the_record():
    """`shard_health` and the chain_server's one-line banner name the
    device that answers (null for the scalar backend)."""
    from gethsharding_tpu.rpc.server import RPCServer
    from gethsharding_tpu.sigbackend import get_backend
    from gethsharding_tpu.smc.chain import SimulatedMainchain

    server = RPCServer(SimulatedMainchain(), sig_backend=get_backend("jax"))
    assert server.rpc_health()["device"] == get_backend("jax").device_record
    scalar = RPCServer(SimulatedMainchain(),
                       sig_backend=get_backend("python"))
    assert scalar.rpc_health()["device"] is None

    proc = subprocess.Popen(
        [sys.executable, "-m", "gethsharding_tpu.rpc.chain_server",
         "--sigbackend", "jax", "--runtime", "0.2"],
        cwd=REPO, stdout=subprocess.PIPE, text=True,
        env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"})
    try:
        banner = json.loads(proc.stdout.readline())
    finally:
        assert proc.wait(timeout=60) == 0
    assert banner["sigbackend"] == "jax"
    assert banner["device"]["platform"] == "cpu"
    assert banner["device"]["count"] >= 1
    assert banner["device"]["compile_cache_dir"]


def test_requested_pallas_kernel_never_gets_the_xla_path(monkeypatch):
    """The selector, not the chip: off the CPU the pairing check's
    Pallas kernels run or raise — a failure to resolve the backend or to
    compile the kernel propagates instead of selecting the XLA path."""
    import jax
    import jax.numpy as jnp

    from gethsharding_tpu.ops import bn256_jax, limb, pallas_finalexp

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert limb._pallas_wanted() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert limb._pallas_wanted() is False

    def no_backend():
        raise RuntimeError("backend init failed")

    monkeypatch.setattr(jax, "default_backend", no_backend)
    with pytest.raises(RuntimeError, match="backend init failed"):
        limb._pallas_wanted()

    def refused(f):
        raise NotImplementedError("Mosaic refused the kernel")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert bn256_jax.pairing_in_pallas() is True
    monkeypatch.setattr(pallas_finalexp, "finalexp_is_one", refused)
    with pytest.raises(NotImplementedError, match="Mosaic refused"):
        bn256_jax.pairing_is_one(
            jnp.zeros((2, 6, 2, limb.NLIMBS), jnp.int32))


# == the cache rule =========================================================

_ENTRY_POINTS = ("gethsharding_tpu.rpc.chain_server", "gethsharding_tpu.cli",
                 "gethsharding_tpu.node.cli", "gethsharding_tpu.fleet.frontend",
                 "chip_smoke")
_CACHE_PROBE = (
    "import importlib, jax\n"
    "from gethsharding_tpu.ops.device import configure_compile_cache\n"
    f"for name in {_ENTRY_POINTS!r}:\n"
    "    importlib.import_module(name)\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "print(configure_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n")


def test_cache_dir_follows_the_environment(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, importing every entry point
    and configuring the cache leaves JAX's directory at that value."""
    want = str(tmp_path / "placed-from-outside")
    proc = _run(_CACHE_PROBE, {"JAX_COMPILATION_CACHE_DIR": want,
                               "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [want, want, want]


def test_cache_dir_defaults_to_the_fixed_checkout_path():
    """Unset, every process gets <checkout>/.jax_cache: a constant of
    the checkout, no host, pid, time or tempfile component."""
    want = os.path.join(REPO, ".jax_cache")
    proc = _run(_CACHE_PROBE, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == ["None", want, want]
    assert device.compile_cache_dir() == os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", want)


def test_this_process_uses_the_one_cache():
    import jax

    assert jax.config.jax_compilation_cache_dir == device.compile_cache_dir()


# == the allocator ==========================================================

_CHURN = """
import resource, threading
from gethsharding_tpu.ops import device

def churn(out):
    before = resource.getrusage(resource.RUSAGE_THREAD).ru_minflt
    for _ in range(12):
        block = bytearray(40 << 20)   # over glibc's largest own threshold
        block[::4096] = b"x" * len(block[::4096])
        del block
    out.append(resource.getrusage(resource.RUSAGE_THREAD).ru_minflt - before)

faults = []
for tuned in (False, True):
    if tuned:
        assert device.keep_freed_memory() is True
    thread = threading.Thread(target=churn, args=(faults,))
    thread.start()
    thread.join()
print(*faults)
"""


def test_a_thread_keeps_what_it_frees_once_told_to():
    """What the TPU host pays five times over (PERF.md, PR 38): a thread
    that is not the main one gives a large block back to the kernel at
    every `free` and faults it in again. In a child: the setting is the
    process's."""
    proc = _run(_CHURN, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr
    plain, kept = map(int, proc.stdout.split())
    assert plain > 100_000 and kept < plain / 5, (plain, kept)


@pytest.mark.parametrize("platform, told", [("cpu", 0), ("tpu", 1)])
def test_the_allocator_is_told_off_the_cpu_and_only_there(
        platform, told, monkeypatch):
    import jax

    class Device:
        device_kind = "a device"

    Device.platform = platform
    calls = []
    monkeypatch.setattr(device, "_resolved", None)
    monkeypatch.setattr(jax, "devices", lambda *a: [Device()])
    monkeypatch.setattr(device, "keep_freed_memory",
                        lambda: calls.append(1))
    assert device.device_record()["platform"] == platform
    assert len(calls) == told


# == the executable store ===================================================


def test_the_store_lies_inside_the_compile_cache():
    assert device.executable_store_dir() == os.path.join(
        device.compile_cache_dir(), "executables")


@pytest.mark.parametrize("platform, engaged", [("cpu", False),
                                               ("tpu", True),
                                               ("gpu", True)])
def test_the_store_engages_by_what_the_device_record_says(
        platform, engaged, monkeypatch, tmp_path):
    from gethsharding_tpu.sigbackend.execstore import ExecutableStore

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    store = ExecutableStore.for_device({"platform": platform})
    assert (store is not None) == engaged
    if engaged:
        assert store.root == str(tmp_path / "executables")
        assert os.path.dirname(store.dir) == store.root


@pytest.mark.parametrize("variable", [
    "GETHSHARDING_EXEC_STORE", "GETHSHARDING_TPU_EXEC_STORE",
    "GETHSHARDING_EXECUTABLE_STORE", "GETHSHARDING_STORE"])
def test_on_the_cpu_the_store_is_off_and_no_variable_turns_it_on(
        variable, monkeypatch):
    from gethsharding_tpu.sigbackend import JaxSigBackend

    monkeypatch.setenv(variable, "1")
    backend = JaxSigBackend()
    assert backend.device_record["platform"] == "cpu"
    assert backend._exec_store is None


def test_a_mesh_backend_takes_no_store(tmp_path):
    """The mesh keeps `_mesh_exec`, in-process only: a store handed to
    a mesh layout is not used."""
    from gethsharding_tpu.sigbackend import JaxSigBackend
    from gethsharding_tpu.sigbackend.execstore import ExecutableStore

    backend = JaxSigBackend(mesh_devices=2,
                            exec_store=ExecutableStore(str(tmp_path)))
    assert backend._layout.is_mesh and backend._exec_store is None


# == chip_smoke.py ==========================================================


def test_chip_smoke_rehearsal_passes_and_the_real_run_fails_off_chip():
    """`chip_smoke.py --rehearsal` drives the served path at 2x3 on the
    CPU (leg A: the chain_server child, the period three ways, one
    request per other kernel family, every verdict against the scalar
    reference) and exits 0 saying `rehearsal` and `cpu` on every line;
    the same command without the flag exits non-zero off the chip and
    prints no result."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    cmd = [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--legs", "A"]
    proc = subprocess.run(cmd + ["--rehearsal"], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["ok"] is True and result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"
    assert all("rehearsal" in ln and "cpu" in ln for ln in lines)
    assert any("period x3 ok" in ln for ln in lines)

    real = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=300)
    assert real.returncode != 0
    assert "not 'tpu'" in real.stderr
    assert '"ok"' not in real.stdout
