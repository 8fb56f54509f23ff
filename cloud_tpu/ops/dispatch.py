"""Shared dispatch plumbing for the Pallas kernel modules.

Rules every kernel module needs identically:

- :func:`force_interpret` — the ``CLOUD_TPU_FLASH_FORCE_INTERPRET=1`` env
  contract (CPU rigs — the unit suite, the driver's virtual-mesh dryrun —
  set it to exercise real kernel code paths through the Pallas interpreter
  instead of silently taking jnp references).  One implementation so the
  contract cannot drift between ops.
- :func:`kernel_mesh` / :func:`dividing_axes` — a kernel under a mesh of
  more than one device runs in a full-manual ``shard_map`` (JAX cannot
  partition a Mosaic call by itself, and libtpu refuses
  ``custom_partitioning`` on more than one chip: "Custom emitter for
  CustomSPMDPartitioning not found"); these say over which mesh, and
  which of the mesh axes THE CALLER names split a given dimension.  The
  ops know no sharding rules of their own: the caller passes the axes
  its operands are split over, as it passes ``mesh=``.
"""

from __future__ import annotations

import os


def force_interpret() -> bool:
    return os.environ.get("CLOUD_TPU_FLASH_FORCE_INTERPRET", "") == "1"


def kernel_mesh(mesh=None):
    """The mesh a kernel call has to be ``shard_map``-ped over: ``mesh``
    (default: the framework's global mesh) when it spans more than one
    device and the call is not already inside a manual region (where the
    shapes are per-shard already); else None — call the kernel directly."""
    from cloud_tpu.parallel import mesh as mesh_lib
    from cloud_tpu.parallel import sharding as sharding_lib

    mesh = mesh if mesh is not None else mesh_lib.get_global_mesh()
    if (mesh is None or mesh.size == 1
            or sharding_lib.manual_context_mesh() is not None):
        return None
    return mesh


def dividing_axes(mesh, axes, size: int):
    """The mesh axes to split a dimension of ``size`` over: those of
    ``axes`` (what the CALLER's sharding rules assign to the dimension —
    a name, a tuple of names, or None for "not split", as in a
    PartitionSpec) that ``mesh`` has with more than one device, taken
    while their product divides it; None when there is none."""
    if axes is None:
        return None
    if isinstance(axes, str):
        axes = (axes,)
    taken, product, shape = [], 1, dict(mesh.shape)
    for axis in axes:
        n = shape.get(axis, 1)
        if n > 1 and size % (product * n) == 0:
            taken.append(axis)
            product *= n
    return tuple(taken) or None
