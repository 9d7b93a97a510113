"""Pallas TPU kernels — the hand-tuned hot path.

The reference's equivalent layer is its CUDA kernel corpus
(`src/operator/nn/*.cu`, cuDNN bindings, mshadow expression templates).  Here
XLA generates almost everything; Pallas kernels are reserved for the ops
where explicit VMEM blocking beats XLA's default schedule — attention above
all (the reference predates flash attention entirely; SURVEY.md §5
"Long-context: absent").

Each entry point takes its kernel or the same mathematics in lax by one
rule, ``common.kernel_impl`` (docs/KERNELS.md; CPU oracle testing —
SURVEY.md §4 test strategy).
"""
from .common import kernel_impl, kernel_unit, kernel_units  # noqa: F401
from .flash_attention import flash_attention, flash_attention_lse  # noqa: F401
from .int8_matmul import int8_matmul, int8_matmul_lax  # noqa: F401
from .layers import fused_rmsnorm, fused_softmax_xent  # noqa: F401
from .grouped_matmul import grouped_matmul, grouped_matmul_lax  # noqa: F401
from .selective_scan import selective_scan, selective_scan_lax  # noqa: F401
from .short_conv import gated_short_conv, gated_short_conv_lax  # noqa: F401

__all__ = ["flash_attention", "flash_attention_lse",
           "fused_rmsnorm", "fused_softmax_xent",
           "grouped_matmul", "grouped_matmul_lax",
           "int8_matmul", "int8_matmul_lax",
           "selective_scan", "selective_scan_lax",
           "gated_short_conv", "gated_short_conv_lax",
           "kernel_impl", "kernel_unit", "kernel_units"]
