"""Device mesh construction.

The mesh is the TPU-native replacement for the reference's process group
(reference train.py:71): instead of a flat rank/world_size with hand-called
collectives, every device joins a named multi-axis mesh and XLA compiles the
collectives implied by sharding annotations over ICI/DCN.

Axis vocabulary used across the framework:

- ``data``     — pure data parallelism (the reference's only axis; its DDP
  world maps to a 1-D ``('data',)`` mesh).
- ``fsdp``     — data parallelism whose param/optimizer state is sharded
  (ZeRO-style); batch is sharded over (data, fsdp) jointly.
- ``tensor``   — tensor (operator) parallelism inside layers.
- ``sequence`` — sequence/context parallelism (ring attention).
- ``expert``   — expert parallelism (MoE layers' expert dim).
- ``pipe``     — pipeline parallelism (GPipe stages, parallel/pipeline.py).

``MeshSpec`` sizes multiply to the device count; -1 means "absorb the rest"
(at most one axis).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import numpy as np


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Named axis sizes for the global device mesh."""

    data: int = -1
    fsdp: int = 1
    tensor: int = 1
    sequence: int = 1
    expert: int = 1
    pipe: int = 1

    def resolve(self, n_devices: int) -> "MeshSpec":
        sizes = dataclasses.asdict(self)
        unknown = [k for k, v in sizes.items() if v == -1]
        if len(unknown) > 1:
            raise ValueError(f"At most one mesh axis may be -1, got {unknown}")
        known = math.prod(v for v in sizes.values() if v != -1)
        if unknown:
            if n_devices % known != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {known}"
                )
            sizes[unknown[0]] = n_devices // known
        elif known != n_devices:
            raise ValueError(
                f"Mesh axes product {known} != device count {n_devices}"
            )
        return MeshSpec(**sizes)

    @property
    def axis_names(self) -> Sequence[str]:
        return ("data", "fsdp", "tensor", "sequence", "expert", "pipe")

    def axis_sizes(self) -> Sequence[int]:
        return (self.data, self.fsdp, self.tensor, self.sequence, self.expert, self.pipe)


def _num_slices(devices) -> int:
    """Distinct TPU slices among ``devices`` (1 = single slice / unknown)."""
    ids = {getattr(d, "slice_index", None) for d in devices}
    if None in ids:
        return 1
    return len(ids)


def _hybrid_shapes(spec: "MeshSpec", n_slices: int):
    """(per_slice_shape, dcn_shape) for a multi-slice mesh, or None.

    Policy: the slice boundary (DCN — orders of magnitude slower than ICI)
    lands on a batch axis — ``data`` first, else ``fsdp`` — whose gradient
    all-reduce / param all-gather are the collectives most tolerant of DCN
    latency (they overlap compute); every other axis stays inside a slice
    on ICI. Requires the chosen axis size % n_slices == 0.
    """
    if n_slices <= 1:
        return None
    sizes = list(spec.axis_sizes())
    for axis in (0, 1):  # 'data', then 'fsdp' (ZeRO configs run data=1)
        if sizes[axis] % n_slices == 0:
            per_slice = list(sizes)
            dcn = [1] * len(sizes)
            per_slice[axis] = sizes[axis] // n_slices
            dcn[axis] = n_slices
            return tuple(per_slice), tuple(dcn)
    return None


def _hybrid_device_array(per_slice, dcn, devices, n_slices):
    """Device array for a multi-slice mesh: DCN boundary on one axis.

    First choice is jax's ``create_hybrid_device_mesh`` (TPU devices carry
    ``slice_index``); environments whose devices don't (virtual CPU slices
    in tests/dryruns, where the slice structure is declared via
    ``make_mesh(n_slices=...)``) get a manual construction: the device
    list is partitioned into ``n_slices`` contiguous groups, each group
    laid out as its own per-slice mesh, and the groups concatenated along
    the DCN axis — so crossing that axis IS crossing the slice boundary.
    """
    from jax.experimental import mesh_utils

    try:
        return mesh_utils.create_hybrid_device_mesh(
            per_slice, dcn, devices=devices
        )
    except Exception:
        if getattr(devices[0], "slice_index", None) is not None:
            # real multi-slice devices where jax's own construction failed:
            # the manual layout below may not respect physical slice
            # membership if the list isn't slice-contiguous — surface it
            from distributed_pytorch_example_tpu.runtime.logging import (
                get_logger,
            )

            get_logger(__name__).warning(
                "create_hybrid_device_mesh failed on devices that carry "
                "slice_index; building the hybrid layout manually by "
                "grouping on slice_index — verify the mesh if slices are "
                "unevenly populated"
            )
    if getattr(devices[0], "slice_index", None) is not None:
        # group by the devices' actual slice membership, not list order
        by_slice = {}
        for d in devices:
            by_slice.setdefault(d.slice_index, []).append(d)
        groups = [by_slice[k] for k in sorted(by_slice)]
    else:
        # virtual slices (CPU tests/dryruns): contiguous list-order groups
        groups = [
            devices[
                i * (len(devices) // n_slices):
                (i + 1) * (len(devices) // n_slices)
            ]
            for i in range(n_slices)
        ]
    try:
        slice_arrays = []
        for g in groups:
            try:
                slice_arrays.append(
                    mesh_utils.create_device_mesh(per_slice, devices=g)
                )
            except Exception:
                slice_arrays.append(np.array(g).reshape(per_slice))
        axis = dcn.index(n_slices)
        return np.concatenate(slice_arrays, axis=axis)
    except Exception:
        # e.g. unevenly populated slices after partial loss: a group can't
        # fill per_slice. Degrade to the naive layout (caller warns) rather
        # than killing the job at mesh construction.
        return None


def make_mesh(
    spec: Optional[MeshSpec] = None,
    devices: Optional[Sequence] = None,
    n_slices: Optional[int] = None,
):
    """Build a ``jax.sharding.Mesh`` over all (or given) devices.

    Default: every device on the ``data`` axis — the direct TPU equivalent of
    the reference's DDP world (train.py:233), with the remaining axes size-1 so
    the same partition specs work unchanged at any parallelism config.

    Uses ``mesh_utils.create_device_mesh`` when spanning all devices so the
    axis order matches the physical ICI topology (fastest-varying axes get
    the tightest links). Multi-slice jobs (devices spanning several TPU
    slices connected over DCN) get a hybrid mesh with the slice dimension
    on the ``data`` axis — see :func:`_hybrid_shapes`. ``n_slices``
    overrides slice detection for devices that don't report
    ``slice_index`` (virtual CPU slices in tests/dryruns).
    """
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    spec = (spec or MeshSpec()).resolve(len(devices))
    shape = tuple(spec.axis_sizes())
    if n_slices is None:
        n_slices = _num_slices(devices)
    if n_slices > 1 and len(devices) % n_slices:
        raise ValueError(
            f"{len(devices)} devices not divisible into {n_slices} slices"
        )
    spans_all = (
        len(devices) == len(jax.devices()) and devices == list(jax.devices())
    )
    hybrid = _hybrid_shapes(spec, n_slices)
    if hybrid is not None:
        per_slice, dcn = hybrid
        dev_array = _hybrid_device_array(per_slice, dcn, devices, n_slices)
        if dev_array is None:  # degraded: fall through to naive + warning
            hybrid = None
            dev_array = np.array(devices).reshape(shape)
    elif spans_all:
        try:
            dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
        except Exception:
            dev_array = np.array(devices).reshape(shape)
    else:
        dev_array = np.array(devices).reshape(shape)
    if n_slices > 1 and hybrid is None:
        from distributed_pytorch_example_tpu.runtime.logging import (
            get_logger,
        )

        get_logger(__name__).warning(
            "multi-slice job (%d slices) fell back to a naive device "
            "layout: the mesh is NOT DCN-aware and cross-slice links may "
            "land inside ICI axes. Check that a batch axis (data/fsdp) is "
            "divisible by the slice count.",
            n_slices,
        )
    return Mesh(dev_array, spec.axis_names)


def current_mesh():
    """The mesh of the enclosing ``with mesh:`` context, or None.

    Lets modules deep inside a model (e.g. ring attention) find the active
    mesh without threading it through every constructor.
    """
    # private import: narrow except so a JAX relayout fails loudly here
    # instead of silently disabling every mesh-aware op
    try:
        from jax._src.mesh import thread_resources
    except (ImportError, AttributeError) as e:
        raise RuntimeError(
            "jax moved jax._src.mesh.thread_resources; update "
            "runtime.mesh.current_mesh for this jax version"
        ) from e
    mesh = thread_resources.env.physical_mesh
    # ``Mesh.empty``, not ``devices.size``: the empty mesh's device array is
    # 0-d, whose size is 1
    return None if mesh.empty else mesh


def free_mesh_axes():
    """``(mesh, axes)``: the mesh axes that are NOT manual at this point of
    the trace, and the ``mesh=`` argument a nested ``jax.shard_map`` over
    them takes (None inside an enclosing shard_map, whose context mesh it
    then inherits).

    A Mosaic (Pallas TPU) kernel cannot be partitioned automatically: on
    more than one device it must sit in a region that is manual over EVERY
    mesh axis, or the lowering refuses it. Callers wrap the kernel in a
    shard_map over these axes; ``axes == ()`` means no wrap is needed
    (one device, no mesh, or already fully manual).
    """
    import jax

    abstract = jax.sharding.get_abstract_mesh()
    if not abstract.empty and abstract.manual_axes:
        # inside a shard_map: its context mesh knows what is manual already
        manual = set(abstract.manual_axes)
        return None, tuple(a for a in abstract.axis_names if a not in manual)
    mesh = current_mesh()
    if mesh is None or mesh.devices.size == 1:
        return None, ()
    return mesh, tuple(mesh.axis_names)


def data_axes(mesh) -> Sequence[str]:
    """The mesh axes a global batch is sharded over (data + fsdp)."""
    return tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)


def data_parallel_size(mesh) -> int:
    return math.prod(mesh.shape[a] for a in data_axes(mesh))
