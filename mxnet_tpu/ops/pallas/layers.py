"""Fused layer kernels: RMSNorm and softmax cross-entropy.

TPU-native replacements for the reference's fused layer kernels
(`/root/reference/src/operator/nn/layer_norm.cc`,
`src/operator/nn/softmax-inl.h`, `src/operator/softmax_output-inl.h`):
one VMEM pass instead of separate normalize/scale (RMSNorm) or
softmax/log/gather (cross-entropy) HBM round-trips.

Called with ``interpret=None`` both ask ``common.kernel_impl`` and take the
kernel or the same mathematics in lax (the parity oracle in tests);
``interpret=True`` / ``False`` force the kernel, interpreted or compiled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import _NEG, _round_up, kernel_impl

__all__ = ["fused_rmsnorm", "fused_softmax_xent"]


# ---------------------------------------------------------------------------
# fused RMSNorm
# ---------------------------------------------------------------------------

def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps, n_feat):
    xf = x_ref[:].astype(jnp.float32)                  # (br, E)
    var = jnp.sum(xf * xf, axis=1, keepdims=True) / n_feat
    r = jax.lax.rsqrt(var + eps)
    o_ref[:] = ((xf * r).astype(o_ref.dtype)
                * scale_ref[:].astype(o_ref.dtype))


def _rmsnorm_lax(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def _rmsnorm_fwd_pallas(x2, scale, eps, block_rows, interpret):
    N, E = x2.shape
    call = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps, n_feat=E),
        name="rmsnorm_fwd",
        grid=(N // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, E), lambda i: (i, 0)),
                  pl.BlockSpec((1, E), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_rows, E), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, E), x2.dtype),
        interpret=interpret,
    )
    with jax.named_scope("rmsnorm_fwd"):
        return call(x2, scale.reshape(1, E))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rmsnorm_op(x2, scale, eps, block_rows, interpret):
    return _rmsnorm_fwd_pallas(x2, scale, eps, block_rows, interpret)


def _rmsnorm_op_fwd(x2, scale, eps, block_rows, interpret):
    return _rmsnorm_fwd_pallas(x2, scale, eps, block_rows, interpret), \
        (x2, scale)


def _rmsnorm_op_bwd(eps, block_rows, interpret, res, g):
    # Elementwise + row-reduce math: XLA fuses this into two passes; a
    # dedicated Pallas backward buys nothing here (bandwidth-bound already).
    x2, scale = res
    xf = x2.astype(jnp.float32)
    gf = g.astype(jnp.float32) * scale.astype(jnp.float32)
    E = x2.shape[1]
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)
    dx = (gf * r - xf * (jnp.sum(gf * xf, -1, keepdims=True) / E) * r ** 3)
    dscale = jnp.sum(g.astype(jnp.float32) * xf * r, axis=0)
    return dx.astype(x2.dtype), dscale.astype(scale.dtype)


_rmsnorm_op.defvjp(_rmsnorm_op_fwd, _rmsnorm_op_bwd)


def fused_rmsnorm(x, scale, eps=1e-6, interpret=None):
    """RMSNorm over the last axis: ``x * rsqrt(mean(x^2) + eps) * scale``.

    x: [..., E]; scale: [E].
    """
    if interpret is None:
        impl = kernel_impl("fused_rmsnorm")
        if impl == "fallback":
            return _rmsnorm_lax(x, scale, eps)
        interpret = impl == "interpret"
    E = x.shape[-1]
    lead = x.shape[:-1]
    N = 1
    for d in lead:
        N *= d
    x2 = x.reshape(N, E)
    block_rows = min(256, _round_up(N, 8))
    pad = _round_up(N, block_rows) - N
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = _rmsnorm_op(x2, scale, float(eps), block_rows, interpret)
    if pad:
        out = out[:N]
    return out.reshape(*lead, E)


# ---------------------------------------------------------------------------
# fused softmax cross-entropy
# ---------------------------------------------------------------------------

def _xent_fwd_kernel(logits_ref, labels_ref, loss_ref, lse_ref,
                     m_ref, l_ref, gold_ref, *, block_v, n_class):
    vi = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        gold_ref[:] = jnp.zeros_like(gold_ref)

    s = logits_ref[:].astype(jnp.float32)              # (br, bv)
    br, bv = s.shape
    col = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, (br, bv), 1)
    valid = col < n_class
    s = jnp.where(valid, s, _NEG)

    label = labels_ref[:]                              # (br, 1) int32
    hit = (col == label) & valid
    gold_ref[:, :1] += jnp.sum(jnp.where(hit, s, 0.0), axis=1, keepdims=True)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    l_ref[:, :1] = (l_ref[:, :1] * alpha
                    + jnp.sum(jnp.exp(s - m_new), axis=1, keepdims=True))
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(vi == nv - 1)
    def _():
        lse = m_ref[:, :1] + jnp.log(l_ref[:, :1])
        lse_ref[:] = lse
        loss_ref[:] = lse - gold_ref[:, :1]


def _xent_bwd_kernel(logits_ref, labels_ref, lse_ref, g_ref, dlogits_ref, *,
                     block_v, n_class):
    vi = pl.program_id(1)
    s = logits_ref[:].astype(jnp.float32)
    br, bv = s.shape
    col = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, (br, bv), 1)
    valid = col < n_class
    p = jnp.where(valid, jnp.exp(s - lse_ref[:, :1]), 0.0)
    onehot = ((col == labels_ref[:]) & valid).astype(jnp.float32)
    dlogits_ref[:] = ((p - onehot) * g_ref[:, :1]).astype(dlogits_ref.dtype)


def _xent_lax(logits, labels):
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    return lse - gold


def _xent_pallas_fwd(l2, lab2, block_r, block_v, n_class, interpret):
    N, Vp = l2.shape
    call = pl.pallas_call(
        functools.partial(_xent_fwd_kernel, block_v=block_v, n_class=n_class),
        name="xent_fwd",
        grid=(N // block_r, Vp // block_v),
        in_specs=[pl.BlockSpec((block_r, block_v), lambda i, j: (i, j)),
                  pl.BlockSpec((block_r, 1), lambda i, j: (i, 0))],
        out_specs=[pl.BlockSpec((block_r, 1), lambda i, j: (i, 0)),
                   pl.BlockSpec((block_r, 1), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((N, 1), jnp.float32),
                   jax.ShapeDtypeStruct((N, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_r, 128), jnp.float32),
                        pltpu.VMEM((block_r, 128), jnp.float32),
                        pltpu.VMEM((block_r, 128), jnp.float32)],
        interpret=interpret,
    )
    with jax.named_scope("xent_fwd"):
        loss, lse = call(l2, lab2)
    return loss[:, 0], lse[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _xent_op(l2, lab2, block_r, block_v, n_class, interpret):
    loss, _ = _xent_pallas_fwd(l2, lab2, block_r, block_v, n_class, interpret)
    return loss


def _xent_op_fwd(l2, lab2, block_r, block_v, n_class, interpret):
    loss, lse = _xent_pallas_fwd(l2, lab2, block_r, block_v, n_class,
                                 interpret)
    return loss, (l2, lab2, lse)


def _xent_op_bwd(block_r, block_v, n_class, interpret, res, g):
    l2, lab2, lse = res
    N, Vp = l2.shape
    call = pl.pallas_call(
        functools.partial(_xent_bwd_kernel, block_v=block_v, n_class=n_class),
        name="xent_bwd",
        grid=(N // block_r, Vp // block_v),
        in_specs=[pl.BlockSpec((block_r, block_v), lambda i, j: (i, j)),
                  pl.BlockSpec((block_r, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((block_r, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((block_r, 1), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((block_r, block_v), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, Vp), l2.dtype),
        interpret=interpret,
    )
    with jax.named_scope("xent_bwd"):
        dlogits = call(l2, lab2, lse.reshape(N, 1), g.reshape(N, 1))
    return dlogits, None


_xent_op.defvjp(_xent_op_fwd, _xent_op_bwd)


def fused_softmax_xent(logits, labels, interpret=None):
    """Per-example softmax cross-entropy: ``logsumexp(logits) - logits[label]``.

    logits: [..., V]; labels: [...] integer.  Returns loss with shape
    ``labels.shape`` (f32).  Differentiable in ``logits`` (fused Pallas
    backward computes ``(softmax - onehot) * g`` without materializing the
    probability tensor in a separate pass).
    """
    if interpret is None:
        impl = kernel_impl("fused_softmax_xent")
        if impl == "fallback":
            return _xent_lax(logits, labels)
        interpret = impl == "interpret"
    V = logits.shape[-1]
    lead = logits.shape[:-1]
    N = 1
    for d in lead:
        N *= d
    l2 = logits.reshape(N, V)
    lab2 = labels.reshape(N, 1).astype(jnp.int32)
    block_r = min(64, _round_up(N, 8))
    block_v = min(2048, _round_up(V, 128))
    pad_r = _round_up(N, block_r) - N
    pad_v = _round_up(V, block_v) - V
    if pad_v:
        l2 = jnp.pad(l2, ((0, 0), (0, pad_v)))
    if pad_r:
        l2 = jnp.pad(l2, ((0, pad_r), (0, 0)))
        lab2 = jnp.pad(lab2, ((0, pad_r), (0, 0)))
    loss = _xent_op(l2, lab2, block_r, block_v, V, interpret)
    if pad_r:
        loss = loss[:N]
    return loss.reshape(lead)

