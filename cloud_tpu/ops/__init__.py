"""Pallas TPU kernels for the hot ops, with reference fallbacks.

Kernels live here, not in models/: a model expresses *what* to compute
with logical-axis sharding; ops/ owns *how* the inner loop maps onto
MXU/VMEM (pallas_guide.md).  Every op has a pure-jnp reference
implementation used off-TPU (and as the ground truth in tests); dispatch
is automatic.
"""

from cloud_tpu.ops.flash_attention import flash_attention
from cloud_tpu.ops.fused_cross_entropy import fused_linear_cross_entropy
from cloud_tpu.ops.group_norm import group_norm
from cloud_tpu.ops.paged_attention import (
    paged_chunk_attention,
    paged_decode_attention,
    paged_verify_attention,
)

__all__ = [
    "flash_attention",
    "fused_linear_cross_entropy",
    "group_norm",
    "paged_chunk_attention",
    "paged_decode_attention",
    "paged_verify_attention",
]
