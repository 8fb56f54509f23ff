"""Named device meshes: the framework's parallelism substrate.

Every parallel strategy in this framework is a :class:`jax.sharding.Mesh`
with canonical axis names; models and the trainer consult sharding *rules*
(``sharding.py``), never device lists.  Axis conventions:

==========  =====================================================
``dp``      pure data parallelism — params replicated; maps to the
            slowest links (DCN across slices) because its only
            collective is one gradient all-reduce per step
``pp``      pipeline stages (GPipe-style microbatching, pipeline.py)
``fsdp``    data parallelism with params/optimizer sharded
            (ZeRO-3); wants intra-slice ICI for its all-gathers
``ep``      expert parallelism for MoE layers
``sp``      sequence/context parallelism (ring attention)
``tp``      tensor parallelism (heads/mlp sharding); innermost —
            its collectives are on the hot path of every matmul
==========  =====================================================

The canonical order sorts axes by collective latency tolerance, so the
device mesh puts ``tp`` neighbours on directly-wired ICI links.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import logging
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

logger = logging.getLogger(__name__)

AXIS_DP = "dp"
AXIS_PP = "pp"
AXIS_FSDP = "fsdp"
AXIS_EP = "ep"
AXIS_SP = "sp"
AXIS_TP = "tp"

#: Outermost (DCN-tolerant) to innermost (ICI-hungry).
CANONICAL_AXES: Tuple[str, ...] = (
    AXIS_DP,
    AXIS_PP,
    AXIS_FSDP,
    AXIS_EP,
    AXIS_SP,
    AXIS_TP,
)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Sizes for each canonical axis (missing axes default to 1).

    ``dcn_sizes`` gives, per axis, how much of that axis spans slice
    boundaries (data-center network) rather than ICI; an axis of size 8
    with ``dcn_sizes={"dp": 2}`` is 2 slice-granules x 4 within-slice.
    The planner fills it for multi-slice jobs.
    """

    sizes: Dict[str, int]
    dcn_sizes: Dict[str, int] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        for axis in self.sizes:
            if axis not in CANONICAL_AXES:
                raise ValueError(
                    f"Unknown mesh axis {axis!r}; canonical axes are "
                    f"{CANONICAL_AXES}"
                )
            if self.sizes[axis] < 1:
                raise ValueError(f"Axis {axis!r} must have size >= 1")
        for axis, dcn in self.dcn_sizes.items():
            if axis not in CANONICAL_AXES:
                raise ValueError(f"Unknown DCN axis {axis!r}")
            if dcn < 1 or self.size(axis) % dcn:
                raise ValueError(
                    f"DCN granule {dcn} must divide axis {axis!r} size "
                    f"{self.size(axis)}"
                )

    @property
    def dcn_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in CANONICAL_AXES if self.dcn_sizes.get(a, 1) > 1)

    def size(self, axis: str) -> int:
        return self.sizes.get(axis, 1)

    @property
    def num_devices(self) -> int:
        return math.prod(self.sizes.values()) if self.sizes else 1

    def axis_names(self) -> Tuple[str, ...]:
        return CANONICAL_AXES

    def shape(self) -> Tuple[int, ...]:
        return tuple(self.size(a) for a in CANONICAL_AXES)

    def nontrivial_axes(self) -> List[str]:
        return [a for a in CANONICAL_AXES if self.size(a) > 1]

    def build(self, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
        """Materialize a Mesh over ``devices`` (default: all devices).

        Uses ``mesh_utils.create_device_mesh`` so the ICI topology is
        respected on real TPU slices (nearest-neighbour axes get wired
        links); on CPU/virtual platforms it degrades to a reshape.  For
        multi-slice specs (``dcn_axes`` non-empty) the hybrid helper lays
        DCN axes across slice granules.
        """
        if devices is None:
            devices = jax.devices()
        devices = list(devices)
        if len(devices) != self.num_devices:
            raise ValueError(
                f"MeshSpec wants {self.num_devices} devices "
                f"(sizes={self.sizes}), got {len(devices)}"
            )
        shape = self.shape()
        from jax.experimental import mesh_utils

        try:
            if self.dcn_axes:
                dcn_shape = tuple(
                    self.dcn_sizes.get(a, 1) for a in CANONICAL_AXES
                )
                ici_shape = tuple(
                    s // d for s, d in zip(shape, dcn_shape)
                )
                arr = mesh_utils.create_hybrid_device_mesh(
                    ici_shape, dcn_shape, devices=devices
                )
            else:
                arr = mesh_utils.create_device_mesh(shape, devices=devices)
        except Exception as e:
            # mesh_utils needs real TPU topology metadata; on CPU/virtual
            # platforms a plain reshape is equivalent.  On real TPU a
            # failure here means the plan doesn't fit the hardware — never
            # silently degrade the layout there.
            if any(d.platform != "cpu" for d in devices):
                raise
            logger.debug("mesh_utils unavailable (%s); reshaping devices", e)
            arr = np.asarray(devices).reshape(shape)
        return Mesh(arr, CANONICAL_AXES)

    # --- wire format (job specs carry the plan into the container) ---

    def to_json(self) -> str:
        return json.dumps({"sizes": self.sizes, "dcn_sizes": self.dcn_sizes})

    @classmethod
    def from_json(cls, data: str) -> "MeshSpec":
        obj = json.loads(data)
        return cls(sizes=obj["sizes"], dcn_sizes=obj.get("dcn_sizes", {}))


# --- global mesh registry -------------------------------------------------
#
# The bootstrap runner (core/bootstrap.py) plans and installs the mesh before
# the user script runs; user code retrieves it here.  This is the analogue of
# the reference setting the global tf.distribute strategy via
# `experimental_set_strategy` in the generated prologue (preprocess.py:148).

_GLOBAL_MESH: Optional[Mesh] = None


def set_global_mesh(mesh: Optional[Mesh]) -> None:
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


def get_global_mesh() -> Optional[Mesh]:
    return _GLOBAL_MESH


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Install ``mesh`` as the global mesh for the duration of the block.

    Enters via ``jax.set_mesh`` (the sharding-in-types context), not the
    legacy ``with mesh:`` block, so that a partial-manual region (the
    pipeline body) finds its abstract context mesh.
    """
    prev = get_global_mesh()
    set_global_mesh(mesh)
    try:
        with jax.set_mesh(mesh):
            yield mesh
    finally:
        set_global_mesh(prev)
