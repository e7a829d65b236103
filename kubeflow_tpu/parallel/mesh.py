"""Device-mesh construction for TPU slices.

TPU-first replacement for the reference's pod-topology + NCCL world layout
(Kubeflow training-operator injects MASTER_ADDR/RANK per pod and delegates the
actual communicator to NCCL inside user containers; see SURVEY.md §2.6/§2.7).
Here the mesh IS the communicator: we build a `jax.sharding.Mesh` with named
axes and let XLA compile collectives onto ICI/DCN from sharding annotations.

Axis vocabulary (all strategies from SURVEY.md §2.6 compose on one mesh):
  data    pure data parallelism (replicated params, all-reduce grads)
  fsdp    sharded data parallelism (ZeRO-3 style param/grad/opt sharding)
  pipe    pipeline stages (microbatched, collective_permute between stages)
  tensor  megatron-style intra-layer model parallelism
  seq     sequence/context parallelism (ring attention / all-to-all)
  expert  MoE expert parallelism (all-to-all token routing)

Multi-slice: `dcn_data`/`dcn_pipe` factors place the slowest-varying mesh dim
across slices so only DP/PP gradients ride DCN while tensor/seq/expert
collectives stay on intra-slice ICI.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical axis order: slowest-communicating axes first so that, on real
# hardware, DCN-crossing axes map to the outermost device dimension and
# tensor/seq (most chatty) map to contiguous ICI neighbours.
MESH_AXES = ("data", "fsdp", "pipe", "tensor", "seq", "expert")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Parallelism degrees. Product must divide the device count (a value of
    -1 for exactly one axis means "absorb all remaining devices")."""

    data: int = -1
    fsdp: int = 1
    pipe: int = 1
    tensor: int = 1
    seq: int = 1
    expert: int = 1
    # Number of slices the job spans; >1 builds a two-level mesh where the
    # slice index becomes the slowest-varying factor of the `data` (or, if
    # data doesn't divide, `pipe`) axis — so only DP gradient all-reduces /
    # PP boundary permutes cross DCN while fsdp/tensor/seq/expert
    # collectives stay on intra-slice ICI (SURVEY.md §5.8(c), eval config 5).
    num_slices: int = 1

    def dcn_axis(self, num_devices: int) -> str | None:
        """Which mesh axis carries the cross-slice (DCN) factor.

        Preference: data (gradient all-reduce tolerates DCN latency), then
        pipe (one boundary permute per microbatch), then seq — the ring-
        attention-across-pods long-context configuration, where each ring
        step's K/V permute is sized to overlap with the step's attention
        compute (SURVEY.md §5.7); chatty axes (fsdp/tensor/expert) never
        cross DCN."""
        if self.num_slices <= 1:
            return None
        sizes = dict(zip(MESH_AXES, self.axis_sizes(num_devices)))
        for axis in ("data", "pipe", "seq"):
            if sizes[axis] % self.num_slices == 0:
                return axis
        raise ValueError(
            f"num_slices={self.num_slices} must divide the data, pipe, or "
            f"seq axis; got mesh {sizes}")

    def axis_sizes(self, num_devices: int) -> tuple[int, ...]:
        sizes = [self.data, self.fsdp, self.pipe, self.tensor, self.seq, self.expert]
        wild = [i for i, s in enumerate(sizes) if s == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {sizes}")
        fixed = math.prod(s for s in sizes if s != -1)
        if wild:
            if num_devices % fixed:
                raise ValueError(
                    f"{num_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wild[0]] = num_devices // fixed
        if math.prod(sizes) != num_devices:
            raise ValueError(
                f"mesh {dict(zip(MESH_AXES, sizes))} needs {math.prod(sizes)} devices, "
                f"have {num_devices}"
            )
        return tuple(sizes)


def _slice_groups(
    devices: Sequence[jax.Device], num_slices: int
) -> list[list[jax.Device]]:
    """Partition devices into per-slice groups, slice-major.

    Preference order mirrors how slices actually manifest: real multi-slice
    TPU devices carry `slice_index`; the emulated multi-slice e2e runs one
    process per slice (group by `process_index`); single-process virtual
    meshes fall back to contiguous blocks (the driver's dryrun)."""
    n = len(devices)
    if n % num_slices:
        raise ValueError(f"{n} devices not divisible by {num_slices} slices")
    per = n // num_slices
    for attr in ("slice_index", "process_index"):
        keys = {getattr(d, attr, None) for d in devices}
        if None not in keys and len(keys) == num_slices:
            groups = [
                [d for d in devices if getattr(d, attr) == k]
                for k in sorted(keys)
            ]
            if all(len(g) == per for g in groups):
                return groups
    return [list(devices[i * per:(i + 1) * per]) for i in range(num_slices)]


def build_mesh(
    config: MeshConfig | None = None,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build the global mesh. On real multi-host TPU, `jax.devices()` is already
    ordered so contiguous devices share ICI; `mesh_utils` would refine this for
    specific topologies — we keep row-major order, which is correct for the
    virtual CPU meshes used in tests and for single-slice v5e/v5p defaults.

    With `num_slices > 1` the device array is assembled slice-major: the
    slice index is the outermost factor of the DCN-crossing axis (data,
    else pipe), so every other axis's collectives stay within one slice.
    This is the two-level ICI/DCN layout the reference world gets from
    NCCL rail-aware topology files — here it is just array layout, and XLA
    emits hierarchical collectives from it."""
    config = config or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    sizes = config.axis_sizes(len(devices))
    if config.num_slices > 1:
        s = config.num_slices
        axis = config.dcn_axis(len(devices))
        idx = MESH_AXES.index(axis)
        groups = _slice_groups(devices, s)
        inner = list(sizes)
        inner[idx] //= s
        arr = np.asarray(groups).reshape([s] + inner)
        # Move the slice factor so it leads the DCN axis, then merge.
        perm = list(range(1, idx + 1)) + [0] + list(range(idx + 1, len(inner) + 1))
        dev_array = arr.transpose(perm).reshape(sizes)
    else:
        dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, MESH_AXES)


def single_device_mesh(device: jax.Device | None = None) -> Mesh:
    dev = device or jax.devices()[0]
    return Mesh(np.asarray([dev]).reshape((1,) * len(MESH_AXES)), MESH_AXES)


def mesh_shape(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_like_axes(mesh: Mesh, exclude: str | None = None) -> tuple[str, ...]:
    """The axes of `mesh` over which a batch is sharded (data, fsdp), minus
    `exclude` — the one rule behind every shard_map'd op's batch spec. A
    mesh with neither (a dedicated single-axis ring mesh) gives ()."""
    return tuple(a for a in ("data", "fsdp")
                 if a in mesh.axis_names and a != exclude)


def current_mesh() -> Mesh | None:
    """The mesh installed by `with mesh:` (thread-local). Lets ops like
    ring_attention find the mesh from inside a model without plumbing."""
    # jax 0.9.0 has no public accessor for the `with mesh:` context (the
    # jax.interpreters.pxla alias is deprecated), so this reads the source.
    from jax._src import mesh as mesh_lib

    phys = mesh_lib.thread_resources.env.physical_mesh
    return None if phys.empty else phys
