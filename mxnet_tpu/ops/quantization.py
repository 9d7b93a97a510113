"""INT8 quantization operators (reference: ``src/operator/quantization/`` —
quantize_v2, dequantize, requantize, quantized_conv, quantized_fully_connected,
quantized_pooling, quantized_flatten).

TPU-native: int8 matmul/conv lower to the MXU with int32 accumulation
(``preferred_element_type``) — the XLA analogue of the reference's cuDNN/
MKLDNN int8 kernels.  Quantization is symmetric per-tensor (scale =
max(|min|,|max|)/127, zero-point 0), matching the reference's
``kQuantizeSymmetric`` path for weights and the int8 data path the
calibration driver produces.

Each quantized op follows the reference's 3-output convention:
``(quantized_out, min_out, max_out)`` carrying the represented real range.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .registry import register

__all__ = []

INT8_MAX = 127.0
INT32_MAX = 2147483647.0


def _scale(mn, mx, qmax=INT8_MAX):
    return jnp.maximum(jnp.maximum(jnp.abs(mn), jnp.abs(mx)),
                       1e-10) / qmax


def _int8_dot(data, weight, scale_a=None, scale_b=None):
    """int8 [M, K] x int8 [N, K] contraction (``ops.pallas.int8_matmul``,
    docs/KERNELS.md).  Without scales the raw int32 accumulator; with them
    the fused in-register dequant -> f32."""
    from .pallas.int8_matmul import int8_matmul

    return int8_matmul(data, weight, scale_a, scale_b)


@register("_contrib_quantize_v2", aliases=("quantize_v2",), no_grad=True,
          num_outputs=3)
def _quantize_v2(data, min_calib_range=None, max_calib_range=None,
                 out_type="int8"):
    """fp32 -> int8 (quantize_v2-inl.h).  Without calib ranges the range
    is computed from the data (the reference's online path)."""
    if min_calib_range is None or max_calib_range is None:
        mn = data.min()
        mx = data.max()
    else:
        mn = jnp.asarray(min_calib_range, jnp.float32)
        mx = jnp.asarray(max_calib_range, jnp.float32)
    s = _scale(mn, mx)
    q = jnp.clip(jnp.round(data / s), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    r = s * INT8_MAX
    return q, -r, r


@register("_contrib_dequantize", aliases=("dequantize",), no_grad=True)
def _dequantize(data, min_range, max_range, out_type="float32"):
    """int8 (or int32 accumulator) -> fp32.  min/max describe the real
    range represented by the extreme quantized value of `data`'s dtype."""
    qmax = INT8_MAX if data.dtype == jnp.int8 else INT32_MAX
    return data.astype(jnp.float32) * _scale(min_range, max_range, qmax)


@register("_contrib_requantize", aliases=("requantize",), no_grad=True,
          num_outputs=3)
def _requantize(data, min_range, max_range, min_calib_range=None,
                max_calib_range=None, out_type="int8"):
    """int32 -> int8 (requantize-inl.h): rescale the int32 accumulator
    range onto int8."""
    real = data.astype(jnp.float32) * _scale(min_range, max_range,
                                             INT32_MAX)
    if min_calib_range is not None:
        mn = jnp.asarray(min_calib_range, jnp.float32)
        mx = jnp.asarray(max_calib_range, jnp.float32)
    else:
        mn = real.min()
        mx = real.max()
    s = _scale(mn, mx)
    q = jnp.clip(jnp.round(real / s), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    r = s * INT8_MAX
    return q, -r, r


@register("_contrib_quantized_fully_connected",
          aliases=("quantized_fully_connected",), no_grad=True,
          num_outputs=3,
          input_names=("data", "weight", "min_data", "max_data",
                       "min_weight", "max_weight", "bias", "min_bias",
                       "max_bias"))
def _quantized_fc(data, weight, min_data, max_data, min_weight,
                  max_weight, bias=None, min_bias=None, max_bias=None,
                  num_hidden=None, no_bias=False, flatten=True):
    """int8 x int8 -> int32 matmul on the MXU (quantized_fully_connected.cc).

    The contraction routes through the kernel registry (docs/KERNELS.md):
    the Pallas int8 tile kernel on single-device TPU, this file's original
    XLA lowering elsewhere."""
    if flatten and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    out = _int8_dot(data, weight)
    sd = _scale(min_data, max_data)
    sw = _scale(min_weight, max_weight)
    out_scale = sd * sw
    if not no_bias and bias is not None:
        # bias arrives int8 with its own scale; rescale into the
        # accumulator's scale (reference shifts bias likewise)
        sb = _scale(min_bias, max_bias)
        b32 = jnp.round(bias.astype(jnp.float32) * sb / out_scale) \
            .astype(jnp.int32)
        out = out + b32
    r = out_scale * INT32_MAX
    return out, -r, r


@register("_contrib_quantized_dense", aliases=("quantized_dense",),
          no_grad=True,
          input_names=("data", "weight", "min_data", "max_data",
                       "min_weight", "max_weight", "bias"))
def _quantized_dense(data, weight, min_data, max_data, min_weight,
                     max_weight, bias=None, num_hidden=None, no_bias=False,
                     flatten=True):
    """int8 x int8 matmul with FUSED per-channel dequant -> f32.

    The kernel-first dense path: where ``quantized_fully_connected`` emits
    the raw int32 accumulator plus a range (and a separate ``dequantize``
    pass re-reads it from HBM), this op applies the requantization scale
    ``scale_data * scale_weight`` in-register on the output tile and writes
    f32 once.  ``min_weight``/``max_weight`` may be per-output-channel [N]
    vectors (per-channel weight calibration); ``bias`` is f32 and is added
    after dequant.  Oracle: ``dequantize(quantized_fully_connected(...))``.
    """
    if flatten and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    sd = _scale(min_data, max_data)
    sw = _scale(min_weight, max_weight)
    out = _int8_dot(data, weight, sd, sw)
    if not no_bias and bias is not None:
        out = out + bias.astype(jnp.float32)
    return out


@register("_contrib_quantized_conv", aliases=("quantized_conv",),
          no_grad=True, num_outputs=3,
          input_names=("data", "weight", "min_data", "max_data",
                       "min_weight", "max_weight", "bias", "min_bias",
                       "max_bias"))
def _quantized_conv(data, weight, min_data, max_data, min_weight,
                    max_weight, bias=None, min_bias=None, max_bias=None,
                    kernel=(),
                    stride=(), dilate=(), pad=(), num_filter=1, num_group=1,
                    no_bias=False, layout=None, cudnn_tune=None,
                    cudnn_off=False, workspace=1024):
    n = len(kernel)
    stride = tuple(stride) or (1,) * n
    dilate = tuple(dilate) or (1,) * n
    pad = tuple(pad) or (0,) * n
    spatial = "DHW"[-n:]
    dn = lax.conv_dimension_numbers(
        data.shape, weight.shape,
        ("NC" + spatial, "OI" + spatial, "NC" + spatial))
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad], rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=num_group,
        preferred_element_type=jnp.int32)
    sd = _scale(min_data, max_data)
    sw = _scale(min_weight, max_weight)
    out_scale = sd * sw
    if not no_bias and bias is not None:
        sb = _scale(min_bias, max_bias)
        b32 = jnp.round(bias.astype(jnp.float32) * sb / out_scale) \
            .astype(jnp.int32)
        out = out + b32.reshape((1, -1) + (1,) * n)
    r = out_scale * INT32_MAX
    return out, -r, r


@register("_contrib_quantized_pooling", aliases=("quantized_pooling",),
          no_grad=True, num_outputs=3,
          input_names=("data", "min_data", "max_data"))
def _quantized_pooling(data, min_data, max_data, kernel=(), pool_type="max",
                       stride=(), pad=(), global_pool=False,
                       pooling_convention="valid", count_include_pad=True,
                       cudnn_off=False):
    """Pooling commutes with quantization (same scale in/out)."""
    from .nn import _pooling

    if pool_type == "avg":
        # average in int32 then round back to int8
        out = _pooling(data.astype(jnp.float32), kernel=kernel,
                       pool_type=pool_type, stride=stride, pad=pad,
                       global_pool=global_pool,
                       pooling_convention=pooling_convention,
                       count_include_pad=count_include_pad)
        out = jnp.clip(jnp.round(out), -INT8_MAX, INT8_MAX) \
            .astype(jnp.int8)
    else:
        out = _pooling(data.astype(jnp.float32), kernel=kernel,
                       pool_type=pool_type, stride=stride, pad=pad,
                       global_pool=global_pool,
                       pooling_convention=pooling_convention,
                       count_include_pad=count_include_pad) \
            .astype(jnp.int8)
    return out, min_data, max_data


@register("_contrib_quantized_act", aliases=("quantized_act",),
          no_grad=True, num_outputs=3,
          input_names=("data", "min_data", "max_data"))
def _quantized_act(data, min_data, max_data, act_type="relu"):
    """ReLU in the quantized domain: max(q, 0) under a symmetric scale
    is exactly relu of the dequantized value.  The representable range
    is kept unchanged so the scale (and therefore the int values)
    stays bit-identical — clipping the range to [0, max] would
    re-derive a different scale and silently re-bin every value."""
    if act_type != "relu":
        raise ValueError("quantized_act supports relu only")
    return jnp.maximum(data, 0), min_data, max_data


@register("_contrib_quantized_flatten", aliases=("quantized_flatten",),
          no_grad=True, num_outputs=3,
          input_names=("data", "min_data", "max_data"))
def _quantized_flatten(data, min_data, max_data):
    return data.reshape(data.shape[0], -1), min_data, max_data


@register("_contrib_quantized_elemwise_add",
          aliases=("quantized_elemwise_add",), no_grad=True,
          num_outputs=3,
          input_names=("lhs", "rhs", "min_lhs", "max_lhs", "min_rhs",
                       "max_rhs"))
def _quantized_elemwise_add(lhs, rhs, min_lhs, max_lhs, min_rhs, max_rhs,
                            min_calib_range=None, max_calib_range=None,
                            with_relu=False):
    """int8 + int8 -> int8 under per-input scales (reference:
    quantization/quantized_elemwise_add.cc) — the residual-add rescale
    kernel that keeps resnet skip connections in the quantized domain.
    One fused elementwise kernel: reads two int8 tensors, writes one
    int8 tensor — a quarter of the fp32 seam's HBM traffic, which is
    the entire game on a bandwidth-bound graph (docs/PERF_INT8.md)."""
    # inputs may be int8 tensors OR raw int32 conv/fc accumulators
    # (whose min/max describe the INT32_MAX-scale range, like
    # dequantize) — scale each by its own dtype's quantized max
    qa = INT8_MAX if lhs.dtype == jnp.int8 else INT32_MAX
    qb = INT8_MAX if rhs.dtype == jnp.int8 else INT32_MAX
    sa = _scale(min_lhs, max_lhs, qa)
    sb = _scale(min_rhs, max_rhs, qb)
    if min_calib_range is not None and max_calib_range is not None:
        mag = jnp.maximum(jnp.abs(jnp.asarray(min_calib_range,
                                              jnp.float32)),
                          jnp.abs(jnp.asarray(max_calib_range,
                                              jnp.float32)))
    else:
        # exact bound: |a*sa + b*sb| <= qa*sa + qb*sb
        mag = qa * sa + qb * sb
    so = jnp.maximum(mag, 1e-10) / INT8_MAX
    acc = (lhs.astype(jnp.float32) * (sa / so)
           + rhs.astype(jnp.float32) * (sb / so))
    if with_relu:
        acc = jnp.maximum(acc, 0.0)
    q = jnp.clip(jnp.round(acc), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return q, -mag, mag


@register("_contrib_quantized_concat", aliases=("quantized_concat",),
          no_grad=True, num_outputs=3)
def _quantized_concat(*args, dim=1, num_args=None, min_calib_range=None,
                      max_calib_range=None):
    """Concat int8 tensors that may carry DIFFERENT scales (reference:
    quantization/quantized_concat.cc — the op inception-style branches
    need so the merge stays int8).  Input layout follows the reference:
    ``(data_0..data_{n-1}, min_0, max_0, min_1, max_1, ...)``.  Each
    branch is re-binned onto the widest represented range, then
    concatenated; output range is that common range.  XLA fuses the
    per-branch rescale into the concat's consumers, so unlike the
    fp32-seam path there is no dequant->requant HBM round-trip."""
    n = int(num_args) if num_args else len(args) // 3
    data = args[:n]
    mins = args[n::2]
    maxs = args[n + 1::2]
    # calibrated output range when available (essential when a branch is
    # a raw int32 accumulator, whose REPRESENTABLE range is astronomically
    # loose); else the widest represented magnitude across branches
    if min_calib_range is not None and max_calib_range is not None:
        common = jnp.maximum(jnp.abs(jnp.asarray(min_calib_range,
                                                 jnp.float32)),
                             jnp.abs(jnp.asarray(max_calib_range,
                                                 jnp.float32)))
    else:
        mags = [jnp.maximum(jnp.abs(mn), jnp.abs(mx))
                for mn, mx in zip(mins, maxs)]
        common = mags[0]
        for m in mags[1:]:
            common = jnp.maximum(common, m)
    out_scale = jnp.maximum(common, 1e-10) / INT8_MAX
    rebinned = []
    for d, mn, mx in zip(data, mins, maxs):
        # branches may be int8 OR raw int32 accumulators (scale by the
        # dtype's quantized max, like dequantize/quantized_elemwise_add)
        s = _scale(mn, mx,
                   INT8_MAX if d.dtype == jnp.int8 else INT32_MAX)
        q = jnp.round(d.astype(jnp.float32) * (s / out_scale))
        rebinned.append(
            jnp.clip(q, -INT8_MAX, INT8_MAX).astype(jnp.int8))
    return (jnp.concatenate(rebinned, axis=dim), -common, common)
