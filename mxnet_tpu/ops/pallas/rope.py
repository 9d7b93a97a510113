"""The rotary term on q and k as one Pallas TPU operation with its own
backward: from the rows the fused projection writes to the heads-first
layout the flash kernels take.

The fused-QKV mixer (`models/transformer.py::_qkv`) projects a layer's
input to rows ``x`` [B, T, N D], its ``N`` heads side by side, and flash
takes q, k and v heads first, [B, heads, T, D].  The lax form
(`models/rope.py::rotate_half`) turns q and k in float32 passes of their
own (halves split and joined again) and leaves the transposes to XLA.  Here
one call reads the rows once and writes each **group** of heads (q, k, v)
heads first, turning the groups that turn.  Per head, ``r`` its rotary
width::

    y = x cos + turn(x) sin         turn(x)[c] = x[c + r / 2]   c < r / 2
                                               = x[c - r / 2]   r / 2 <= c < r

rotate_half's ``-b`` carried as the sign of the sine table, and a column at
or past ``r`` passing through (cosine 1, sine 0).  Design:

* **the tables** are `models/rope.py::rope_lane_tables`: ``(cos, sin)``
  [T, 128] float32, the values of ``rope_tables`` laid out a head's columns
  at a time, repeated for each head a row of 128 lanes holds;
* **a row of 128 lanes holds 128 / D heads** (one at D = 128, two at D =
  64) and a head never crosses one: the half swap is a lane roll by ``r /
  2`` each way (one roll where ``r`` is 128) and a select on the column
  within the head.  :func:`select`'s shape rule takes a head width that
  divides 128, a rotary width that divides it, and head counts that fill
  whole rows;
* grid ``(B, T / block_t, head rows / block_h)``, **the heads innermost**:
  the tables' block index does not change across heads, so a position
  block's tables are fetched once a call, not once a head;
* **forward** (``rope_fwd``): the input block is ``block_h`` rows' columns
  of ``x`` by block index (no slice of ``x`` is copied); each group is an
  output [B, n, T, D] whose block holds those rows' heads, the transpose
  riding in the block's DMA.  A step writes one group's block; the other
  groups' index maps hold the block they last wrote or will write next, and
  a block is written back when its index changes, so each is written once
  (the head axis is ``arbitrary``).  Arithmetic in float32, results in
  ``x``'s type; a group that does not turn (v) is copied;
* **backward** (``rope_bwd``): ``dx = dy cos + turn(dy sin)`` (turn is its
  own transpose), one output, the rows [B, T, N D], so the projection's
  gradient is whole with no concatenation.  The groups' cotangents come in
  as ``[B n, T, D]``, a free reshape: no operand of this kernel is heads
  first ``[B, H, T, D]``, the operand by which the benchmark's flash
  readers pick flash's calls.  Nothing is kept for it but the tables;
* ``block_h`` is the largest of 8 / 4 / 2 / 1 rows that divides every
  group's, ``block_t`` all of ``T`` up to the rung where a block of the
  rows passes 1 MiB (512 rows of 8 bfloat16 heads), else that rung and a
  partial last block;
* at trace time the counter ``pallas.rope.<fwd|bwd>.<heads>x<D>.<r>of<D>``
  (the heads that turn; one each time a call is traced: the forward is
  traced twice under differentiation) says which layers took the kernels;
  :func:`select` asks ``common.kernel_impl`` (``pallas.select.rope.<impl>``)
  where the shape rule holds and answers ``fallback`` (counted the same way)
  where it does not: the caller keeps ``rotate_half`` for that, for a mesh
  and for ``MXTPU_PALLAS=off``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import kernel_impl

__all__ = ["select", "takes", "rope_heads"]

_F32 = jnp.float32
_LANES = 128
_H_RUNGS = (8, 4, 2, 1)
_T_MAX = 1024
_BLOCK_BYTES = 1 << 20
_VMEM_LIMIT = 32 * 1024 * 1024


def takes(D, rot, counts):
    """Whether the kernels take heads ``D`` wide, ``rot`` of whose columns
    turn, in groups of ``counts`` heads: every head inside one row of 128
    lanes, the rotary width a divisor of the head's, whole rows a group."""
    return (_LANES % D == 0 and rot % 2 == 0 and D % rot == 0
            and all(n % (_LANES // D) == 0 for n in counts))


def select(D, rot, counts):
    """Whether the kernels turn a layer whose ``counts`` heads (q, k) are
    ``D`` wide, ``rot`` columns turning: ``common.kernel_impl``'s answer
    where :func:`takes` holds, else ``fallback``, counted
    ``pallas.select.rope.<impl>`` either way.  Returns None for
    ``fallback`` (the caller keeps its lax form), else the ``interpret``
    flag of :func:`rope_heads`."""
    impl = kernel_impl("rope", count=False)
    if impl not in ("pallas", "interpret") or not takes(D, rot, counts):
        impl = "fallback"
    from ... import telemetry as _telemetry
    _telemetry.registry().counter("pallas.select.rope." + impl).inc()
    return None if impl == "fallback" else impl == "interpret"


def _turn(x, D, rot):
    """The half swap of each head of ``x`` [rows, 128], heads ``D`` wide:
    column ``c < rot / 2`` of a head takes ``c + rot / 2``, ``rot / 2 <= c
    < rot`` takes ``c - rot / 2``, and a column past ``rot`` takes 0."""
    half = rot // 2
    up = pltpu.roll(x, _LANES - half, 1)            # [c] = x[c + half]
    if rot == _LANES:                               # one head, all turning
        return up
    col = lax.broadcasted_iota(jnp.int32, x.shape, 1) % D
    turned = jnp.where(col < half, up, pltpu.roll(x, half, 1))
    return turned if rot == D else jnp.where(col < rot, turned, 0.0)


def _fwd_kernel(cos_ref, sin_ref, x_ref, *out_refs, spans, D, rot, block_h):
    s = pl.program_id(2)
    per = _LANES // D
    for out_ref, (first, steps, turns) in zip(out_refs, spans):
        @pl.when((s >= first) & (s < first + steps))
        def _(out_ref=out_ref, turns=turns):
            for j in range(block_h):
                y = x_ref[0, :, j * _LANES:(j + 1) * _LANES]
                if turns:
                    x = y.astype(_F32)
                    y = (x * cos_ref[...] + _turn(x, D, rot) * sin_ref[...]
                         ).astype(out_ref.dtype)
                for m in range(per):
                    out_ref[0, j * per + m] = y[:, m * D:(m + 1) * D]


def _bwd_kernel(cos_ref, sin_ref, *refs, spans, D, rot, block_h):
    g_refs, dx_ref = refs[:-1], refs[-1]
    s = pl.program_id(2)
    per = _LANES // D
    for g_ref, (first, steps, turns) in zip(g_refs, spans):
        @pl.when((s >= first) & (s < first + steps))
        def _(g_ref=g_ref, turns=turns):
            for j in range(block_h):
                heads = [g_ref[j * per + m] for m in range(per)]
                g = heads[0] if per == 1 else jnp.concatenate(heads, axis=1)
                if turns:
                    gf = g.astype(_F32)
                    g = (gf * cos_ref[...]
                         + _turn(gf * sin_ref[...], D, rot)
                         ).astype(dx_ref.dtype)
                dx_ref[0, :, j * _LANES:(j + 1) * _LANES] = g


def _plan(groups, D, T, itemsize):
    """``(spans, block_h, block_t, steps)``: each group's ``(first step,
    steps, turns)`` along the head axis, the blocks, the head axis' length."""
    rows = [n * D // _LANES for n, _ in groups]
    block_h = next(h for h in _H_RUNGS if all(r % h == 0 for r in rows))
    block_t = min(_T_MAX, _BLOCK_BYTES // (block_h * _LANES * itemsize))
    block_t = T if T <= block_t else block_t
    spans, first = [], 0
    for r, (_, turns) in zip(rows, groups):
        spans.append((first, r // block_h, turns))
        first += r // block_h
    return tuple(spans), block_h, block_t, first


def _clamped(first, steps):
    """A group's block along its heads at head step ``s``: its own while
    ``s`` is in the group, else the nearest of its own."""
    return lambda s: jnp.clip(s - first, 0, steps - 1)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _count(kind, groups, D, rot):
    from ... import telemetry as _telemetry
    turned = sum(n for n, turns in groups if turns)
    _telemetry.registry().counter("pallas.rope.%s.%dx%d.%dof%d" % (
        kind, turned, D, rot, D)).inc()


def _fwd(x, cos, sin, groups, D, rot, interpret):
    B, T, _ = x.shape
    spans, block_h, bt, steps = _plan(groups, D, T, x.dtype.itemsize)
    hb = block_h * _LANES // D
    table = pl.BlockSpec((bt, _LANES), lambda b, i, s: (i, 0))
    out_specs = []
    for first, steps_g, _turns in spans:
        head = _clamped(first, steps_g)
        out_specs.append(pl.BlockSpec(
            (1, hb, bt, D), lambda b, i, s, head=head: (b, head(s), i, 0)))
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, spans=spans, D=D, rot=rot,
                          block_h=block_h),
        name="rope_fwd",
        grid=(B, pl.cdiv(T, bt), steps),
        in_specs=[table, table,
                  pl.BlockSpec((1, bt, block_h * _LANES),
                               lambda b, i, s: (b, i, s))],
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((B, n, T, D), x.dtype)
                   for n, _ in groups],
        cost_estimate=pl.CostEstimate(
            flops=4 * x.size, transcendentals=0,
            bytes_accessed=2 * x.size * x.dtype.itemsize
            + 2 * cos.size * 4),
        interpret=interpret,
        compiler_params=_params(),
    )
    _count("fwd", groups, D, rot)
    with jax.named_scope("rope_fwd"):
        return tuple(call(cos, sin, x))


def _bwd(gs, cos, sin, groups, D, rot, interpret):
    B, _, T, _ = gs[0].shape
    dtype = gs[0].dtype
    N = sum(n for n, _ in groups)
    spans, block_h, bt, steps = _plan(groups, D, T, dtype.itemsize)
    hb = block_h * _LANES // D
    table = pl.BlockSpec((bt, _LANES), lambda b, i, s: (i, 0))
    in_specs = []
    for first, steps_g, _turns in spans:
        head = _clamped(first, steps_g)
        in_specs.append(pl.BlockSpec(
            (hb, bt, D), lambda b, i, s, per_b=steps_g, head=head: (
                b * per_b + head(s), i, 0)))
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, spans=spans, D=D, rot=rot,
                          block_h=block_h),
        name="rope_bwd",
        grid=(B, pl.cdiv(T, bt), steps),
        in_specs=[table, table] + in_specs,
        out_specs=pl.BlockSpec((1, bt, block_h * _LANES),
                               lambda b, i, s: (b, i, s)),
        out_shape=jax.ShapeDtypeStruct((B, T, N * D), dtype),
        cost_estimate=pl.CostEstimate(
            flops=4 * B * T * N * D, transcendentals=0,
            bytes_accessed=2 * B * T * N * D * dtype.itemsize
            + 2 * cos.size * 4),
        interpret=interpret,
        compiler_params=_params(),
    )
    _count("bwd", groups, D, rot)
    # [B, n, T, D] -> [B n, T, D]: no operand heads first (module docstring)
    flat = [g.reshape(B * g.shape[1], T, D) for g in gs]
    with jax.named_scope("rope_bwd"):
        return call(cos, sin, *flat)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _rope(x, cos, sin, groups, D, rot, interpret):
    return _fwd(x, cos, sin, groups, D, rot, interpret)


def _rope_fwd(x, cos, sin, groups, D, rot, interpret):
    return _fwd(x, cos, sin, groups, D, rot, interpret), (cos, sin)


def _rope_bwd(groups, D, rot, interpret, res, gs):
    cos, sin = res
    # the tables are constants: no cotangent
    return _bwd(gs, cos, sin, groups, D, rot, interpret), None, None


_rope.defvjp(_rope_fwd, _rope_bwd)


def rope_heads(x, cos, sin, groups, D, rot, interpret=False):
    """The rows ``x`` [B, T, N D] as one heads-first array [B, n, T, D] a
    group of ``groups`` (``((n, turns), ...)``, the ``n`` summing to ``N``,
    in the rows' order), a group that ``turns`` turned by ``cos``, ``sin``
    (`models/rope.py::rope_lane_tables` for heads ``D`` wide, ``rot``
    columns turning).  Differentiable in ``x`` through its own backward
    kernel.  ``interpret`` runs the kernels through the Pallas interpreter.
    :func:`select` says whether the kernels take the shape."""
    B, T, F = x.shape
    groups = tuple((int(n), bool(turns)) for n, turns in groups)
    assert F == sum(n for n, _ in groups) * D, (x.shape, groups, D)
    assert takes(D, rot, [n for n, _ in groups]), (D, rot, groups)
    assert cos.shape == sin.shape == (T, _LANES), (cos.shape, T)
    return _rope(x, cos, sin, groups, D, rot, bool(interpret))
