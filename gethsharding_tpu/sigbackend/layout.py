"""Device placement: single device today, the shard mesh when asked.

One resolved `DeviceLayout` per backend instance decides WHERE every
plane lands. The default is the single-device layout the repo has run
since r0 (placement is `jnp.asarray`, the jit path untouched). With
``--mesh-devices``/``GETHSHARDING_MESH_DEVICES`` > 1 the layout builds
a 1-D ``("shard",)`` mesh over `parallel/mesh.make_mesh` and places
every batch plane as ``NamedSharding(P('shard'))`` along the leading
(shardID) axis — the SNIPPETS.md mesh idiom, and the same layout the
multi-chip dryrun proves bit-identical on the virtual CPU platform.

jax stays a lazy import throughout: resolving a single-device layout
must not initialize an accelerator backend (the CPU-only control-plane
contract of the package docstring).
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

from gethsharding_tpu.sigbackend.marshal import bucket_size

MESH_ENV = "GETHSHARDING_MESH_DEVICES"

# HLO op mnemonics that move bytes BETWEEN devices. Async pairs
# (`all-reduce-start`/`-done`) count once, on the start half.
_COLLECTIVE_OPS = ("all-reduce", "all-gather", "all-to-all",
                   "collective-permute", "reduce-scatter")


def mesh_devices_requested(explicit: Optional[int] = None) -> int:
    """The device count this process should lay out over: an explicit
    constructor argument wins, else ``GETHSHARDING_MESH_DEVICES``,
    else 1 (the single-device layout)."""
    if explicit is not None:
        return max(1, int(explicit))
    raw = os.environ.get(MESH_ENV, "").strip()
    return max(1, int(raw)) if raw else 1


def count_collectives(hlo_text: str) -> int:
    """Cross-device collective ops in a compiled HLO module — the
    transfer-ledger check behind the mesh audit's acceptance bar
    (exactly ONE vote-total all-reduce per step)."""
    n = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for op in _COLLECTIVE_OPS:
            if f" {op}(" in stripped or f" {op}-start(" in stripped:
                n += 1
                break
    return n


def count_ops(hlo_text: str, opcode: str) -> int:
    """Occurrences of one HLO opcode in a compiled module — the op
    census behind the precomp path's non-vacuity check: the warm
    precomp executable must carry far fewer `multiply` ops than the
    recompute executable, proving the fixed-argument Miller point
    arithmetic really is absent (same contract as `count_collectives`:
    counted from the optimized AOT text, no second compile)."""
    n = 0
    needle = f" {opcode}("
    for line in hlo_text.splitlines():
        if needle in line.strip():
            n += 1
    return n


class DeviceLayout:
    """Resolved placement for one backend instance.

    ``n_devices == 1``: no mesh, no sharding — `place` is a plain
    default-device transfer and the dispatch path is byte-identical to
    the pre-mesh backend. ``n_devices > 1``: a 1-D ``("shard",)`` mesh
    whose `NamedSharding` splits every leading batch axis into
    contiguous per-device slabs."""

    def __init__(self, n_devices: int = 1):
        self.n_devices = max(1, int(n_devices))
        self.mesh = None
        self.sharding = None
        self.devices: Sequence = ()
        if self.n_devices > 1:
            # lazy: only a mesh layout touches jax (and so the backend)
            from gethsharding_tpu.parallel.mesh import (
                make_mesh, shard_axis_sharding)

            self.mesh = make_mesh(self.n_devices)
            self.sharding = shard_axis_sharding(self.mesh)
            self.devices = list(self.mesh.devices.flat)

    @property
    def is_mesh(self) -> bool:
        return self.mesh is not None

    def shard_spec(self):
        """PartitionSpec splitting the leading axis over every mesh
        axis (the in/out spec of the one-step mesh audit)."""
        from jax.sharding import PartitionSpec

        return PartitionSpec(tuple(self.mesh.axis_names))

    def mesh_bucket(self, n: int) -> int:
        """The mesh batch bucket: `bucket_size`, then rounded up to a
        multiple of the device count so the `NamedSharding` split is
        even (XLA shards contiguous equal slabs; padded rows are masked
        rejections exactly like single-device padding)."""
        bucket = bucket_size(n)
        d = self.n_devices
        return -(-bucket // d) * d

    def rows_per_device(self, bucket: int) -> int:
        return bucket // self.n_devices

    def device_of_row(self, row: int, bucket: int) -> int:
        """Which mesh slot a (padded) batch row lands on under the
        contiguous leading-axis split — the cache sharding function."""
        return min(row // self.rows_per_device(bucket),
                   self.n_devices - 1)

    def place(self, host_array):
        """Ship one host plane: split along the leading axis over the
        mesh (each device receives only its slab's bytes)."""
        import jax

        return jax.device_put(host_array, self.sharding)

    def assemble(self, per_device: Sequence):
        """One global sharded array from per-device slabs already
        resident on their devices — `make_array_from_single_device_
        arrays`, ZERO bytes crossing the host->device link or the
        interconnect (the mesh half of the residency claim)."""
        import jax

        first = per_device[0]
        shape = (first.shape[0] * self.n_devices,) + tuple(first.shape[1:])
        return jax.make_array_from_single_device_arrays(
            shape, self.sharding, list(per_device))
