"""Int8 matmul with fused per-channel dequant as a Pallas TPU kernel.

The reference lowers int8 FullyConnected through generic GEMM
(`/root/reference/src/operator/quantization/quantized_fully_connected.cc`);
here the quantized dense path gets a hand-tiled MXU kernel: int8 x int8
tiles accumulate in an int32 VMEM scratch across the (sequential) K grid
dim, and on the last K step the requantization scale is applied in-register
on the output tile — the dequantized f32 result leaves VMEM once, with no
separate dequantize pass over an int32 intermediate in HBM.

Layouts match `ops/quantization.py`'s FullyConnected: ``a`` is activations
[M, K] int8, ``b`` is the weight [N, K] int8 (contraction over K on both),
``scale_b`` may be per-output-channel [N].  Where ``common.kernel_impl``
says so the public entry takes the XLA lowering (`int8_matmul_lax`,
identical math — the parity oracle); ``interpret=True`` runs the real kernel
through the Pallas interpreter for CPU parity tests.  See docs/KERNELS.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import _round_up, kernel_impl

__all__ = ["int8_matmul", "int8_matmul_lax"]


def _accum(a_ref, b_ref, acc_ref):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # int8 x int8 -> int32 on the MXU (contraction over K for both operands:
    # a (bm, bk), b (bn, bk))
    acc_ref[:] += jax.lax.dot_general(
        a_ref[:], b_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)


def _mm_i32_kernel(a_ref, b_ref, out_ref, acc_ref):
    _accum(a_ref, b_ref, acc_ref)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        out_ref[:] = acc_ref[:]


def _mm_dequant_kernel(a_ref, b_ref, s_ref, out_ref, acc_ref):
    _accum(a_ref, b_ref, acc_ref)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        # fused dequant: per-output-channel scale (1, bn) applied to the
        # int32 tile while it is still in registers
        out_ref[:] = acc_ref[:].astype(jnp.float32) * s_ref[:]


def int8_matmul_lax(a, b, scale_a=None, scale_b=None):
    """XLA lowering of the same contraction — off-TPU fallback and parity
    oracle.  Returns int32 [M, N] without scales, f32 with them."""
    acc = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    if scale_a is None and scale_b is None:
        return acc
    s = jnp.float32(1.0)
    if scale_a is not None:
        s = s * jnp.asarray(scale_a, jnp.float32)
    if scale_b is not None:
        s = s * jnp.asarray(scale_b, jnp.float32)
    return acc.astype(jnp.float32) * s


def _int8_matmul_pallas(a, b, scale_a=None, scale_b=None, block_m=None,
                        block_n=None, block_k=None, interpret=False):
    M, K = a.shape
    N = b.shape[0]
    dequant = scale_a is not None or scale_b is not None
    # int8 min tile is (32, 128); zero padding is exact in int32
    bm = block_m or min(128, _round_up(M, 32))
    bn = block_n or min(128, _round_up(N, 128))
    bk = block_k or min(128, _round_up(K, 128))
    Mp, Np, Kp = _round_up(M, bm), _round_up(N, bn), _round_up(K, bk)
    if (Mp, Kp) != (M, K):
        a = jnp.pad(a, ((0, Mp - M), (0, Kp - K)))
    if (Np, Kp) != (N, K):
        b = jnp.pad(b, ((0, Np - N), (0, Kp - K)))
    grid = (Mp // bm, Np // bn, Kp // bk)

    aspec = pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki))
    bspec = pl.BlockSpec((bn, bk), lambda mi, ni, ki: (ni, ki))
    ospec = pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni))
    cost = pl.CostEstimate(flops=2 * Mp * Np * Kp,
                           bytes_accessed=Mp * Kp + Np * Kp + 4 * Mp * Np,
                           transcendentals=0)
    if dequant:
        s = jnp.float32(1.0)
        if scale_a is not None:
            s = s * jnp.asarray(scale_a, jnp.float32)
        if scale_b is not None:
            s = s * jnp.asarray(scale_b, jnp.float32)
        s = jnp.broadcast_to(s.reshape(1, -1), (1, N)).astype(jnp.float32)
        if Np != N:
            s = jnp.pad(s, ((0, 0), (0, Np - N)))
        call = pl.pallas_call(
            _mm_dequant_kernel,
            name="int8_matmul_dequant",
            grid=grid,
            in_specs=[aspec, bspec,
                      pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni))],
            out_specs=ospec,
            out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
            cost_estimate=cost,
            interpret=interpret,
        )
        with jax.named_scope("int8_matmul_dequant"):
            out = call(a, b, s)
    else:
        call = pl.pallas_call(
            _mm_i32_kernel,
            name="int8_matmul",
            grid=grid,
            in_specs=[aspec, bspec],
            out_specs=ospec,
            out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.int32),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
            cost_estimate=cost,
            interpret=interpret,
        )
        with jax.named_scope("int8_matmul"):
            out = call(a, b)
    if (Mp, Np) != (M, N):
        out = out[:M, :N]
    return out


def int8_matmul(a, b, scale_a=None, scale_b=None, block_m=None, block_n=None,
                block_k=None, interpret=None):
    """``a`` [M, K] int8 x ``b`` [N, K] int8 -> [M, N].

    Without scales returns the raw int32 accumulator (bit-exact against the
    XLA lowering).  With ``scale_a`` (scalar, activation scale) and/or
    ``scale_b`` (scalar or per-output-channel [N], weight scale) the product
    is dequantized in-register on the output tile -> f32 (fused dequant).

    ``interpret=None`` asks ``common.kernel_impl`` (``MXTPU_PALLAS``): the
    kernel on a single-device TPU, the XLA lowering elsewhere.
    ``interpret=True``/``False`` force the kernel through the interpreter /
    compiled.
    """
    if interpret is None:
        impl = kernel_impl("int8_matmul")
        if impl == "fallback":
            return int8_matmul_lax(a, b, scale_a, scale_b)
        interpret = impl == "interpret"
    return _int8_matmul_pallas(a, b, scale_a, scale_b, block_m=block_m,
                               block_n=block_n, block_k=block_k,
                               interpret=interpret)
