"""Pallas TPU kernels — the hand-tuned hot path.

The reference's equivalent layer is its CUDA kernel corpus
(`src/operator/nn/*.cu`, cuDNN bindings, mshadow expression templates).  Here
XLA generates almost everything; Pallas kernels are reserved for the ops
where explicit VMEM blocking beats XLA's default schedule — attention above
all (the reference predates flash attention entirely; SURVEY.md §5
"Long-context: absent").

Kernels fall back to pure-lax implementations off-TPU (CPU oracle testing —
SURVEY.md §4 test strategy).
"""
from .common import (kernel_unit, kernel_units, register_impl,  # noqa: F401
                     select_impl)
from .flash_attention import (flash_attention, flash_attention_lse,  # noqa: F401
                              flash_self_attention)
from .int8_matmul import int8_matmul, int8_matmul_lax  # noqa: F401
from .layers import fused_rmsnorm, fused_softmax_xent  # noqa: F401
from .selective_scan import selective_scan, selective_scan_lax  # noqa: F401

__all__ = ["flash_attention", "flash_attention_lse", "flash_self_attention",
           "fused_rmsnorm", "fused_softmax_xent",
           "int8_matmul", "int8_matmul_lax",
           "selective_scan", "selective_scan_lax",
           "select_impl", "register_impl", "kernel_unit", "kernel_units"]
