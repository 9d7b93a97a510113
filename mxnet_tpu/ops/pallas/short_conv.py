"""The gated short convolution (the convolution mixer of a hybrid LM) as one
Pallas TPU operation with its own backward.

For every channel ``d``, over time, with ``K`` taps ``w [K, D]``::

    v_t = b_t * u_t
    z_t = sum_{j < K} w[j] * v_{t - K + 1 + j}          v = 0 before t = 0
    y_t = c_t * z_t

A depthwise causal convolution between two gates: no matrix product, every
byte read once, so it is bound by HBM bandwidth.  Design:

* grid ``(batch, T / block_t, D / block_d)``, all ``parallel``: a time tile
  needs the ``K - 1`` rows before it (forward) or after it (backward) and
  nothing else, so no state is carried from tile to tile.  Those rows come
  in as a **halo**: the same operand a second time, tiled in blocks of
  ``_HALO`` = 8 rows (one sublane tile, so ``K - 1 <= 8``), the block just
  before (after) the time tile; zeros at the sequence's start (end);
* **forward**, one pass: reads ``b``, ``c``, ``u`` once (and the halo's 8
  rows of ``b`` and ``u``), writes ``y``.  ``v`` goes to a float32 scratch
  of ``8 + block_t`` rows, the halo's in the first 8, so that each tap is a
  load of ``block_t`` rows at a static offset;
* **backward**, one pass: re-makes ``z`` from ``b``, ``u`` and their halo
  before the tile, and ``dz = dy * c`` from ``dy``, ``c`` and their halo
  after it, then ``dc = dy * z``, ``dv_t = sum_j w[j] dz_{t + K - 1 - j}``,
  ``db = dv * u``, ``du = dv * b``; each tile's part of ``dw[j] = sum_t
  dz_t v_{t - K + 1 + j}`` leaves the kernel (``[batch, T / block_t, K,
  D]`` float32) and is summed outside.  The operation keeps its inputs and
  nothing else for the backward;
* ``block_t`` is the longest of 512 / 256 / ... / 8 rows that divides ``T``
  padded to 8, ``block_d`` the widest of 512 / 256 / 128 lanes that divides
  ``D`` (else all of ``D``): at 512 x 512 the backward's eight bfloat16
  tiles double-buffered and its float32 scratch stay under 8 MiB;
* arithmetic in float32 whatever the storage types; results and gradients
  take their inputs' types;
* with ``interpret=None`` the entry point asks ``common.kernel_impl``
  (``pallas.select.short_conv.*``): the kernels, or
  :func:`gated_short_conv_lax`, the same mathematics in ``lax`` whose
  backward autodiff writes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import _round_up, kernel_impl

__all__ = ["gated_short_conv", "gated_short_conv_lax"]

_F32 = jnp.float32
_HALO = 8
_T_RUNGS = (512, 256, 128, 64, 32, 16, 8)
_D_RUNGS = (512, 256, 128)
_VMEM_LIMIT = 32 * 1024 * 1024


def _choose_tiles(T, D):
    """``(block_t, block_d)`` of a ``T`` that is a multiple of 8."""
    block_t = next(r for r in _T_RUNGS if T % r == 0)
    block_d = next((r for r in _D_RUNGS if D % r == 0), D)
    return block_t, block_d


def _taps(ref, start, w, K, rows):
    """``sum_j w[j] * ref[start + j : start + j + rows]`` (``w`` [K, bd])."""
    out = None
    for j in range(K):
        term = ref[pl.ds(start + j, rows), :] * w[j:j + 1]
        out = term if out is None else out + term
    return out


def _fwd_kernel(b_ref, u_ref, c_ref, bh_ref, uh_ref, w_ref, y_ref, v_ref, *,
                K, block_t):
    i = pl.program_id(1)
    halo = bh_ref[0].astype(_F32) * uh_ref[0].astype(_F32)
    v_ref[:_HALO] = jnp.where(i > 0, halo, 0.0)
    v_ref[_HALO:] = b_ref[0].astype(_F32) * u_ref[0].astype(_F32)
    z = _taps(v_ref, _HALO - (K - 1), w_ref[...].astype(_F32), K, block_t)
    y_ref[0] = (c_ref[0].astype(_F32) * z).astype(y_ref.dtype)


def _bwd_kernel(b_ref, u_ref, c_ref, g_ref, bh_ref, uh_ref, cn_ref, gn_ref,
                w_ref, db_ref, dc_ref, du_ref, dw_ref, v_ref, dz_ref, *,
                K, block_t):
    i, n = pl.program_id(1), pl.num_programs(1)
    w = w_ref[...].astype(_F32)
    b, u = b_ref[0].astype(_F32), u_ref[0].astype(_F32)
    g, c = g_ref[0].astype(_F32), c_ref[0].astype(_F32)
    halo = bh_ref[0].astype(_F32) * uh_ref[0].astype(_F32)
    v_ref[:_HALO] = jnp.where(i > 0, halo, 0.0)
    v_ref[_HALO:] = b * u
    dz = g * c
    dz_ref[:block_t] = dz
    after = gn_ref[0].astype(_F32) * cn_ref[0].astype(_F32)
    dz_ref[block_t:] = jnp.where(i < n - 1, after, 0.0)
    z = _taps(v_ref, _HALO - (K - 1), w, K, block_t)
    dc_ref[0] = (g * z).astype(dc_ref.dtype)
    # dv_t = sum_j w[j] dz_{t + K - 1 - j}: tap j at offset K - 1 - j
    dv = None
    for j in range(K):
        term = dz_ref[pl.ds(K - 1 - j, block_t), :] * w[j:j + 1]
        dv = term if dv is None else dv + term
    db_ref[0] = (dv * u).astype(db_ref.dtype)
    du_ref[0] = (dv * b).astype(du_ref.dtype)
    for j in range(K):
        v_j = v_ref[pl.ds(_HALO - (K - 1) + j, block_t), :]
        dw_ref[0, 0, j:j + 1] = jnp.sum(dz * v_j, axis=0, keepdims=True)


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _specs(Tp, block_t, block_d, K):
    tile = pl.BlockSpec((1, block_t, block_d), lambda b, i, d: (b, i, d))
    per = block_t // _HALO
    last = Tp // _HALO - 1
    before = pl.BlockSpec((1, _HALO, block_d), lambda b, i, d: (
        b, jnp.maximum(i * per - 1, 0), d))
    after = pl.BlockSpec((1, _HALO, block_d), lambda b, i, d: (
        b, jnp.minimum((i + 1) * per, last), d))
    taps = pl.BlockSpec((K, block_d), lambda b, i, d: (0, d))
    return tile, before, after, taps


def _fwd(b, c, u, w, block_t, block_d, interpret):
    B, Tp, D = b.shape
    K = w.shape[0]
    tile, before, _after, taps = _specs(Tp, block_t, block_d, K)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, K=K, block_t=block_t),
        name="short_conv_fwd",
        grid=(B, Tp // block_t, D // block_d),
        in_specs=[tile, tile, tile, before, before, taps],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((B, Tp, D), b.dtype),
        scratch_shapes=[pltpu.VMEM((_HALO + block_t, block_d), _F32)],
        cost_estimate=pl.CostEstimate(
            flops=(2 * K + 2) * B * Tp * D, transcendentals=0,
            bytes_accessed=4 * B * Tp * D * b.dtype.itemsize),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )
    with jax.named_scope("short_conv_fwd"):
        return call(b, u, c, b, u, w)


def _bwd(b, c, u, w, g, block_t, block_d, interpret):
    B, Tp, D = b.shape
    K = w.shape[0]
    nt = Tp // block_t
    tile, before, after, taps = _specs(Tp, block_t, block_d, K)
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, K=K, block_t=block_t),
        name="short_conv_bwd",
        grid=(B, nt, D // block_d),
        in_specs=[tile, tile, tile, tile, before, before, after, after, taps],
        out_specs=[tile, tile, tile,
                   pl.BlockSpec((1, 1, K, block_d),
                                lambda b, i, d: (b, i, 0, d))],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, D), b.dtype),
                   jax.ShapeDtypeStruct((B, Tp, D), c.dtype),
                   jax.ShapeDtypeStruct((B, Tp, D), u.dtype),
                   jax.ShapeDtypeStruct((B, nt, K, D), _F32)],
        scratch_shapes=[pltpu.VMEM((_HALO + block_t, block_d), _F32),
                        pltpu.VMEM((block_t + _HALO, block_d), _F32)],
        cost_estimate=pl.CostEstimate(
            flops=(4 * K + 6) * B * Tp * D, transcendentals=0,
            bytes_accessed=7 * B * Tp * D * b.dtype.itemsize),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )
    with jax.named_scope("short_conv_bwd"):
        db, dc, du, dw = call(b, u, c, g, b, u, c, g, w)
    return db, dc, du, jnp.sum(dw, axis=(0, 1)).astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _conv(b, c, u, w, block_t, block_d, interpret):
    return _fwd(b, c, u, w, block_t, block_d, interpret)


def _conv_fwd(b, c, u, w, block_t, block_d, interpret):
    return _fwd(b, c, u, w, block_t, block_d, interpret), (b, c, u, w)


def _conv_bwd(block_t, block_d, interpret, res, g):
    b, c, u, w = res
    return _bwd(b, c, u, w, g, block_t, block_d, interpret)


_conv.defvjp(_conv_fwd, _conv_bwd)


def gated_short_conv_lax(b, c, u, w):
    """The same mathematics in ``lax``: ``K`` shifted multiply-adds of
    ``b * u`` (zeros before the start), times ``c``; float32 inside."""
    K, T = w.shape[0], b.shape[1]
    v = b.astype(_F32) * u.astype(_F32)
    vp = jnp.pad(v, ((0, 0), (K - 1, 0), (0, 0)))
    wf = w.astype(_F32)
    z = None
    for j in range(K):
        term = vp[:, j:j + T] * wf[j]
        z = term if z is None else z + term
    return (c.astype(_F32) * z).astype(b.dtype)


def gated_short_conv(b, c, u, w, interpret=None):
    """``y = c * causal_depthwise_conv(b * u, w)`` over b, c, u [batch, T,
    D] with taps ``w`` [K, D] (tap ``K - 1`` on the step itself, tap 0 on
    the step ``K - 1`` before); ``y`` in ``b``'s type.  With
    ``interpret=None`` by ``common.kernel_impl``: the Pallas kernels above
    (one operation, differentiable in every operand through its own
    backward kernel) or :func:`gated_short_conv_lax`.  ``interpret=True`` /
    ``False`` force the kernels, interpreted or compiled."""
    if interpret is None:
        impl = kernel_impl("short_conv")
        if impl == "fallback":
            return gated_short_conv_lax(b, c, u, w)
        interpret = impl == "interpret"
    B, T, D = b.shape
    K = w.shape[0]
    assert 1 <= K <= _HALO + 1, "a halo of %d rows serves K <= %d" % (
        _HALO, _HALO + 1)
    # zeros after the end: nothing before them reads them going forward,
    # and their gradient is zero going back
    pt = _round_up(T, _HALO) - T
    if pt:
        b, c, u = (jnp.pad(x, ((0, 0), (0, pt), (0, 0))) for x in (b, c, u))
    block_t, block_d = _choose_tiles(T + pt, D)
    y = _conv(b, c, u, w, block_t, block_d, interpret)
    return y[:, :T] if pt else y
