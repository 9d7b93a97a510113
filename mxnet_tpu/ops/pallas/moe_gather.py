"""The expert layer's row gathers as Pallas TPU kernels that move only the
live rows.

`parallel/moe.py::expert_layer` sorts the ``P = S k`` (token, slot) pairs by
expert, the held experts' pairs first: the first ``live = sum(sizes)`` rows
of a ``[P, E]`` buffer are the ones any consumer needs (the grouped product
skips the rest, an absent expert's slot adds 0 to the combine).  XLA's
gathers move all ``P`` rows, and from HBM at about a third of its bandwidth
(PERF.md §6).  Here every gather of the layer is one of two forms:

* **sorted rows** (``moe_gather_rows``): ``rows[r] = src[idx[r]]`` for the
  blocks of rows that hold a live one -- the dispatch's forward,
  ``tokens[order // k]``; and with the combine's backward products folded
  in (``moe_gather_grad``): ``d_out[r] = w[r] g[idx[r]]`` and ``d_w[r] =
  out[r] . g[idx[r]]``, the row of ``g`` read once, in VMEM;
* **slot sum** (``moe_slot_sum``): ``y[s] = sum_j w[s, j] src[inverse[s k +
  j]]`` in float32 over the slots whose row is live, a dead slot adding 0 --
  the combine's forward (no ``[k, S, E]`` intermediate) and the dispatch's
  backward (unit weights).

Design:

* **a row is a DMA.**  The TPU's DMA takes whole tiles of a tiled HBM array
  (a bfloat16 ``[N, E]`` is tiled 8 rows deep, two rows a 32-bit word), so a
  row cannot be fetched alone from the layout the layer's arrays have.  A
  source is first **packed** (``moe_pack``): ``[N, E]`` to ``uint32 [N, 1,
  E / 2]``, word ``j`` of a row the bfloat16 bits of columns ``j`` (low
  half) and ``E / 2 + j`` (high half); a float32 row is its bits.  The
  compiler lays ``[N, 1, W]`` out in tiles of one row, so each row is one
  contiguous DMA of ``2 E`` bytes.  Unpacked in VMEM, the halves are the
  row's columns as float32, exactly: a shift and a mask;
* **only live rows move.**  The live count comes in as scalar prefetch
  beside the index vector.  The packing of a sorted-side source (the down
  product's rows, the dispatch's gradient) reads and writes only the blocks
  that hold a live row (a block past them repeats the last block's index:
  no DMA, and the step does nothing); the sorted gather runs a loop over
  those blocks alone; the slot sum starts no DMA for a dead slot;
* **many rows in flight.**  The gather and the slot sum double-buffer by
  hand: the row DMAs of the next block are started before the current
  block's are waited for (one wait covers a block's rows: the semaphore
  counts bytes), and a finished block leaves by DMA from a staging buffer
  while the next is gathered;
* **what is left unwritten.**  Rows in a block past the last live one are
  never written (packed sources, sorted rows, ``d_out``; ``d_w``'s entries
  past the live count).  Every consumer skips or masks them: the grouped
  product computes no tile past its groups and masks its rows at a group's
  boundary (its ``dW`` masks both operands), the slot sum reads only live
  rows, ``d_w`` moved back to the slots is replaced by 0 where a slot's
  row is past the live count.  The rows of the block that holds the last
  live one are all written (past ``live`` with the rows their indices
  name);
* blocks of up to ``_ROWS`` sorted rows and ``_TOKENS`` tokens (the largest
  rung of 512 ... 8 that divides the count); sums in float32; results take
  the source's type;
* the entry points are jitted: an LM step calls each with one set of
  shapes in every expert layer and pass, and a nested jit traces and lowers
  its kernels once for all of them (the set-up's time);
* every ``pallas_call`` has a ``name=`` and a ``jax.named_scope`` of the
  same name (``moe_pack``, ``moe_gather_rows``, ``moe_gather_grad``,
  ``moe_slot_sum``); :func:`select` asks ``common.kernel_impl``
  (``pallas.select.moe_gather.<impl>``) where the kernels take the shape
  and, on the chip, the ``[P, E]`` buffer is larger than the fast memory
  (``_XLA_FAST_BYTES``), else answers ``fallback`` (counted the same way):
  ``parallel/moe.py`` keeps its lax forms for that, for a mesh and for
  ``MXTPU_PALLAS=off``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import kernel_impl

__all__ = ["select", "sorted_rows", "sorted_rows_grad", "slot_sum"]

_F32 = jnp.float32
_U32 = jnp.uint32
_RUNGS = (512, 256, 128, 64, 32, 16, 8)
# rows a block of the sorted gather, and token rows a block of the slot sum
# (k slots of each in flight): big enough that a block's DMAs hide the
# start of the next block's, small enough for VMEM
_ROWS = 256
_TOKENS = 128
_VMEM_LIMIT = 64 * 1024 * 1024
_HIGH = 0xFFFF0000
# XLA's own gathers where the layer's [P, E] buffer is at most this: XLA
# then places their sources in the v5e's 128 MiB of fast memory and is the
# faster.  On the v5e, a call alone (ms), XLA's against the kernels': at
# [24576, 2048] bfloat16 (100.7 MB; 4,096 tokens, top-6) the dispatch's
# rows 0.20 / 0.26, the slot sum 0.48 / 0.92, the combine's backward
# 0.47 / 0.38; at [98304, 2560] (503 MB; 16,384 tokens, top-6) 0.79 / 1.04,
# 4.86 / 3.00, 3.03 / 0.97 (PERF.md §6)
_XLA_FAST_BYTES = 128 * 1024 * 1024


def _rows_a_block(n, cap):
    """The largest rung up to ``cap`` that divides ``n`` (None: none)."""
    return next((r for r in _RUNGS if r <= cap and n % r == 0), None)


def _takes(S, k, E, dtype, interpret):
    """Whether the kernels take the layer's shape: bfloat16 or float32 rows,
    token and pair counts a whole number of blocks, and for Mosaic a packed
    row a whole number of 128-lane vectors (half of a bfloat16 row: the two
    halves are its low and high words)."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)):
        return False
    if dtype.itemsize == 2 and E % 2:
        return False
    if not interpret and _width(E, dtype) % 128:
        return False
    T = _rows_a_block(S, _TOKENS)
    # a listed slot's code, its row shifted past its place in the block
    return T is not None and _rows_a_block(S * k, _ROWS) is not None and \
        S * k << (k * T - 1).bit_length() < 2 ** 31


def select(S, k, E, dtype):
    """The implementation the expert layer's gathers take for ``S`` tokens
    of ``k`` slots, ``E`` wide: ``common.kernel_impl``'s answer where the
    kernels take the shape, else ``fallback``; and ``fallback`` on the chip
    where the layer's ``[P, E]`` buffer fits the fast memory
    (``_XLA_FAST_BYTES``).  The counter ``pallas.select.moe_gather.<impl>``
    says which, once a layer."""
    from ... import telemetry as _telemetry
    from ...dispatch import pallas_mode
    impl = "fallback"
    if _takes(S, k, E, dtype, pallas_mode() == "interpret"):
        impl = kernel_impl("moe_gather", count=False)
        if impl == "pallas" and S * k * E * jnp.dtype(
                dtype).itemsize <= _XLA_FAST_BYTES:
            impl = "fallback"
    _telemetry.registry().counter("pallas.select.moe_gather." + impl).inc()
    return impl


# ---------------------------------------------------------------------------
# packed rows: [N, E] -> uint32 [N, 1, W], a row one contiguous DMA
# ---------------------------------------------------------------------------

def _pack(x):
    """[R, E] bfloat16 or float32 -> uint32 [R, W]."""
    if x.dtype == _F32:
        return lax.bitcast_convert_type(x, _U32)
    W = x.shape[1] // 2
    lo = lax.bitcast_convert_type(x[:, :W].astype(_F32), _U32) >> 16
    hi = lax.bitcast_convert_type(x[:, W:].astype(_F32), _U32) & _U32(_HIGH)
    return hi | lo


def _unpack(w, dtype):
    """uint32 [R, W] -> the rows' values as float32 [R, E]."""
    if jnp.dtype(dtype) == jnp.dtype(_F32):
        return lax.bitcast_convert_type(w, _F32)
    return jnp.concatenate([lax.bitcast_convert_type(w << 16, _F32),
                            lax.bitcast_convert_type(w & _U32(_HIGH), _F32)],
                           axis=1)


def _width(E, dtype):
    return E // 2 if jnp.dtype(dtype).itemsize == 2 else E


def _last_block(live, R):
    return jnp.maximum((live + R - 1) // R - 1, 0)


def _pack_kernel(live_ref, x_ref, o_ref, *, R):
    @pl.when(pl.program_id(0) * R < live_ref[0])
    def _():
        o_ref[...] = _pack(x_ref[...]).reshape(o_ref.shape)


def _packed(x, live, interpret):
    """``x`` [N, E] -> uint32 [N, 1, W]: the blocks that hold one of the
    first ``live`` [1] rows; the others are left unwritten."""
    N, E = x.shape
    W = _width(E, x.dtype)
    R = _rows_a_block(N, 512)

    def block(b, live):
        return jnp.minimum(b, _last_block(live[0], R))

    call = pl.pallas_call(
        functools.partial(_pack_kernel, R=R),
        name="moe_pack",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N // R,),
            in_specs=[pl.BlockSpec((R, E), lambda b, live: (block(b, live),
                                                            0))],
            out_specs=pl.BlockSpec((R, 1, W), lambda b, live: (
                block(b, live), 0, 0))),
        out_shape=jax.ShapeDtypeStruct((N, 1, W), _U32),
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=N * E * x.dtype.itemsize + N * W * 4),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
    )
    with jax.named_scope("moe_pack"):
        return call(live, x)


# ---------------------------------------------------------------------------
# sorted rows: rows[r] = src[idx[r]], and the combine's backward products
# ---------------------------------------------------------------------------

def _column(row, R):
    """(1, R) -> (R, 1): the row broadcast over 128 sublanes, transposed."""
    return jnp.transpose(jnp.broadcast_to(row, (128, R)))[:, :1]


def _line(col, R):
    """(R, 1) -> (1, R)."""
    return jnp.transpose(jnp.broadcast_to(col, (R, 128)))[:1]


def _rows_kernel(idx_ref, live_ref, src_hbm, *rest, R, dtype, grad):
    if grad:
        (out_hbm, w_hbm, rows_hbm, dw_hbm, buf, stage, obuf, wbuf, dwbuf,
         sem_in, sem_aux, sem_out) = rest
    else:
        rows_hbm, buf, stage, sem_in, sem_out = rest
    live = live_ref[0]
    n = (live + R - 1) // R
    W = buf.shape[-1]

    def fetch(b, slot):
        base = b * R

        def eight(e, carry):
            for u in range(8):
                r = e * 8 + u
                pltpu.make_async_copy(src_hbm.at[idx_ref[base + r]],
                                      buf.at[slot, r], sem_in.at[slot]).start()
            return carry

        lax.fori_loop(0, R // 8, eight, 0)
        if grad:
            pltpu.make_async_copy(out_hbm.at[pl.ds(base, R)], obuf.at[slot],
                                  sem_aux.at[slot]).start()
            pltpu.make_async_copy(w_hbm.at[b], wbuf.at[slot],
                                  sem_aux.at[slot]).start()

    def fetched(slot):
        # one wait for the block's R row copies: the semaphore counts bytes
        pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                              sem_in.at[slot]).wait()
        if grad:
            pltpu.make_async_copy(obuf.at[slot], obuf.at[slot],
                                  sem_aux.at[slot]).wait()
            pltpu.make_async_copy(wbuf.at[slot], wbuf.at[slot],
                                  sem_aux.at[slot]).wait()

    def put(b, slot):
        copies = [pltpu.make_async_copy(stage.at[slot],
                                        rows_hbm.at[pl.ds(b * R, R)],
                                        sem_out.at[slot])]
        if grad:
            copies.append(pltpu.make_async_copy(dwbuf.at[slot], dw_hbm.at[b],
                                                sem_out.at[slot]))
        return copies

    @pl.when(n > 0)
    def _():
        fetch(0, 0)

    def step(b, carry):  # mxlint: disable-block=TS002
        slot = b % 2

        @pl.when(b + 1 < n)
        def _():
            fetch(b + 1, 1 - slot)

        fetched(slot)

        @pl.when(b >= 2)
        def _():
            for c in put(b - 2, slot):
                c.wait()

        x = _unpack(buf[slot].reshape(R, W), dtype)
        if grad:
            w = _column(wbuf[slot], R)
            stage[slot] = (x * w).astype(stage.dtype)
            dw = jnp.sum(obuf[slot].astype(_F32) * x, axis=1, keepdims=True)
            dwbuf[slot] = _line(dw, R)
        else:
            stage[slot] = x.astype(stage.dtype)
        for c in put(b, slot):
            c.start()
        return carry

    lax.fori_loop(0, n, step, 0)
    for last in (n - 2, n - 1):
        @pl.when(last >= 0)
        def _():
            for c in put(last, last % 2):
                c.wait()


def _sorted_call(src, idx, live, interpret, out=None, w_rows=None):
    S, E = src.shape
    P = idx.shape[0]
    dtype = src.dtype
    W = _width(E, dtype)
    R = _rows_a_block(P, _ROWS)
    grad = out is not None
    name = "moe_gather_grad" if grad else "moe_gather_rows"
    packed = _packed(src, jnp.full((1,), S, jnp.int32), interpret)
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    scratch = [pltpu.VMEM((2, R, 1, W), _U32), pltpu.VMEM((2, R, E), dtype)]
    out_shape = [jax.ShapeDtypeStruct((P, E), dtype)]
    operands = [packed]
    if grad:
        scratch += [pltpu.VMEM((2, R, E), out.dtype),
                    pltpu.VMEM((2, 1, R), _F32), pltpu.VMEM((2, 1, R), _F32),
                    pltpu.SemaphoreType.DMA((2,))]
        out_shape.append(jax.ShapeDtypeStruct((P // R, 1, R), _F32))
        operands += [out, w_rows.astype(_F32).reshape(P // R, 1, R)]
    scratch += [pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,))]
    call = pl.pallas_call(
        functools.partial(_rows_kernel, R=R, dtype=dtype, grad=grad),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[any_] * len(operands),
            out_specs=[any_] * len(out_shape),
            scratch_shapes=scratch),
        out_shape=out_shape,
        cost_estimate=pl.CostEstimate(
            flops=3 * P * E if grad else 0, transcendentals=0,
            bytes_accessed=P * E * dtype.itemsize * (3 if grad else 2)),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
    )
    with jax.named_scope(name):
        return call(idx, live, *operands)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sorted_rows(src, idx, live, interpret=False):
    """``rows[r] = src[idx[r]]`` [P, E] for the blocks of rows that hold
    one of the first ``live`` (int32 [1]); the rows of later blocks are
    left unwritten.  ``src`` [S, E]; ``idx`` int32 [P], every entry a row
    of ``src``."""
    return _sorted_call(src, idx, live, interpret)[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def sorted_rows_grad(g, idx, live, out, w_rows, interpret=False):
    """The combine's backward in the sorted rows' space: with ``g_r =
    g[idx[r]]`` as float32, ``d_out[r] = w_rows[r] g_r`` in ``g``'s type
    [P, E] and ``d_w[r] = out[r] . g_r`` float32 [P], for the blocks that
    hold one of the first ``live`` rows (the rest unwritten)."""
    d_out, d_w = _sorted_call(g, idx, live, interpret, out, w_rows)
    return d_out, d_w.reshape(-1)


# ---------------------------------------------------------------------------
# slot sum: y[s] = sum_j w[s, j] src[inverse[s k + j]] over the live slots
# ---------------------------------------------------------------------------

def _slot_sum_kernel(code_ref, levels_ref, wm_ref, src_hbm, y_ref, buf, acc,
                     lst, count, sem, *, T, k, shift, dtype):
    i = pl.program_id(0)
    W = buf.shape[-1]
    log_t = T.bit_length() - 1

    def fetch(blk, slot):
        """The block's live slots listed without a branch -- each pair's
        code, its token's place in the block added, is stored, and the
        list's end moves past it unless the code is negative (a dead slot)
        -- then one DMA each."""
        base = blk * T * k

        def token(t, n):  # mxlint: disable-block=TS002
            for j in range(k):
                code = code_ref[base + t * k + j]
                lst[slot, n] = code + t
                n = n + 1 + (code >> 31)
            return n

        n = lax.fori_loop(0, T, token, jnp.int32(0))
        count[slot] = n

        def start(q, carry):
            code = lst[slot, q]
            to = code & ((1 << shift) - 1)
            pltpu.make_async_copy(src_hbm.at[code >> shift],
                                  buf.at[slot, to >> log_t, to & (T - 1)],
                                  sem.at[slot]).start()
            return carry

        lax.fori_loop(0, n, start, 0)

    @pl.when(i == 0)
    def _():
        fetch(0, 0)

    @pl.when(i + 1 < pl.num_programs(0))
    def _():
        fetch(i + 1, (i + 1) % 2)

    slot = i % 2
    n = count[slot]

    # the semaphore counts bytes: a wait for eight rows at a time, then one
    # for each row left
    def wait(rows):
        def one(q, carry):
            pltpu.make_async_copy(buf.at[slot, 0, pl.ds(0, rows)],
                                  buf.at[slot, 0, pl.ds(0, rows)],
                                  sem.at[slot]).wait()
            return carry
        return one

    lax.fori_loop(0, n // 8, wait(8), 0)
    lax.fori_loop(0, n % 8, wait(1), 0)
    wm = wm_ref[...]

    def term(c):
        rows = _unpack(buf[slot, c].reshape(T, W), dtype)
        return jnp.where(wm[:, k + c:k + c + 1] > 0, rows * wm[:, c:c + 1],
                         0.0)

    # level by level, in slot order as the lax form adds them (a dead
    # slot's 0 apart); a level no token of the block reaches is skipped
    acc[...] = term(0)
    for c in range(1, k):
        @pl.when(c < levels_ref[i])
        def _():
            acc[...] += term(c)

    y_ref[...] = acc[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def slot_sum(src, inverse, live, weights, k, interpret=False):
    """``y[s] = sum_j weights[s, j] * src[inverse[s k + j]]`` [S, E] in
    float32, in ``src``'s type, over the slots whose row is one of the first
    ``live`` (int32 [1]) of ``src`` [P, E]; a dead slot adds 0.  ``weights``
    [S, k] (None: 1).

    A token's ``c``-th live slot (in slot order) lands in level ``c`` of
    its block.  What the kernel reads of a pair is made here, in vector
    code: its code -- the row shifted past its place ``c T + t`` in the
    block, less the token's ``t`` (the kernel adds it: an iota of ``S``
    made here would be held from the forward through the step's peak), or
    -1 where the slot is dead -- the weights and mask moved to the levels,
    and each block's number of levels."""
    P, E = src.shape
    S = P // k
    dtype = src.dtype
    W = _width(E, dtype)
    T = _rows_a_block(S, _TOKENS)
    shift = (k * T - 1).bit_length()
    packed = _packed(src, live, interpret)
    rows = inverse.reshape(S, k)
    alive = rows < live[0]
    level = jnp.cumsum(alive.astype(jnp.int32), axis=1) - 1
    code = jnp.where(alive, (rows << shift) + level * T, -1).reshape(P)
    # a block's levels: the most live slots a token of it has (a max from
    # 0: from jnp.max's int32 minimum, that constant is held all step)
    levels = lax.reduce(level[:, -1].reshape(S // T, T) + 1, jnp.int32(0),
                        lax.max, (1,))
    onehot = alive[:, :, None] & (level[:, :, None] == jnp.arange(k))
    w = _F32(1) if weights is None else weights.astype(_F32)[:, :, None]
    wm = jnp.concatenate(
        [jnp.sum(jnp.where(onehot, w, 0.0), axis=1),
         jnp.any(onehot, axis=1).astype(_F32)], axis=1)
    call = pl.pallas_call(
        functools.partial(_slot_sum_kernel, T=T, k=k, shift=shift,
                          dtype=dtype),
        name="moe_slot_sum",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S // T,),
            in_specs=[pl.BlockSpec((T, 2 * k), lambda i, code, lv: (i, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((T, E), lambda i, code, lv: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, k, T, 1, W), _U32),
                            pltpu.VMEM((T, E), _F32),
                            pltpu.SMEM((2, k * T), jnp.int32),
                            pltpu.SMEM((2,), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((S, E), dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * P * E, transcendentals=0,
            bytes_accessed=(P + S) * E * dtype.itemsize),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
    )
    with jax.named_scope("moe_slot_sum"):
        return call(code, levels.astype(jnp.int32), wm, packed)
