"""Flash attention as a Pallas TPU kernel (forward + backward).

This is the framework's hand-tuned hot path — the TPU counterpart of the
reference's cuDNN-backed attention-adjacent kernels (the reference predates
flash attention entirely; its kernel corpus lives in
`/root/reference/src/operator/nn/` and `src/operator/nn/cudnn/`).  Design:

* layout [B, T, H, D] at the API (matching `parallel/ring_attention.py`),
  [B, H, T, D] inside the kernels; ``v`` (and with it ``o``, ``do``,
  ``dv``) may have a width ``Dv`` of its own, narrower or wider than the
  ``D`` of ``q`` and ``k`` (latent attention: 192 for the scores, 128 for
  the values);
* grid (B, H, num_q_blocks, num_k_blocks), ``parallel`` x 3 and
  ``arbitrary`` on the innermost (sequential) axis, so f32 VMEM scratch
  accumulators implement the streaming-softmax recurrence across k blocks
  exactly like the lax fallback (`blockwise_attention`);
* tiles come from the shape (``_choose_tiles``): one tile of
  ``_round_up(T, 8)`` up to T = 128, else the largest rung of 1024 / 512 /
  256 / 128 that divides ``_round_up(T, 128)`` and whose working set fits
  the scoped VMEM the call asks for; ``block_q`` / ``block_k`` override;
* under ``causal`` a tile wholly above the diagonal is neither fetched (its
  ``index_map`` clamps to the last needed tile, so no DMA is issued) nor
  computed (``pl.when``); the mask runs only on tiles the diagonal crosses
  or that hold key padding; with ``window`` (``W``: query ``t`` sees the
  keys ``0 <= t - j < W``) the inner grid axis is as long as the longest run
  of inner tiles that any outer tile's band touches, not as the sequence
  (``_inner_axis``: 5 steps of 16 at T = 16,384, W = 4,096, tiles of 1,024),
  and step ``j`` is the tile ``first(i) + j``, in the index maps and in the
  kernels alike; a tile an edge of the band crosses is masked, one inside
  runs bare, and the few steps a cut band leaves over are neither fetched
  nor computed; ``window=None`` builds the causal kernels;
* forward saves per-row logsumexp; backward recomputes probabilities from
  (q, k, lse) in two Pallas kernels (dq over k blocks; dk/dv over q blocks)
  — no O(T^2) residuals;
* under differentiation ``o`` and ``lse`` carry ``checkpoint_name``s
  (``SAVED_NAMES``): a rematerialised layer that keeps both does not run
  the forward kernel a second time;
* operands reach the MXU in the input's dtype (``p`` and ``ds`` are cast to
  it; float32 callers keep float32 products), accumulated in f32 via
  ``preferred_element_type``; scores, softmax statistics, ``lse``,
  ``delta`` and all accumulators are f32 regardless of input dtype;
* at trace time the counter ``pallas.flash.tile.<kernel>.<bq>x<bk>`` and the
  gauge ``pallas.flash.causal_tiles_run_share`` (tiles on or under the
  diagonal / tiles of the grid, last traced kernel) record the schedule, and
  for a call with a window ``pallas.flash.window.<kernel>.<W>`` and the gauge
  ``pallas.flash.band_tiles_run_share`` (tiles that touch the band / tiles
  of the ``nq x nk`` grid); for every call
  ``pallas.flash.inner_steps.<kernel>.<steps>of<tiles>`` (the inner axis'
  length, of the sequence's inner tiles) and the gauge
  ``pallas.flash.grid_steps_run_share`` (tiles that run / grid steps);
* called with ``interpret=None`` the public entry points ask
  ``common.kernel_impl``: the kernels, the kernels inside a ``shard_map``
  over the mesh's batch and head axes (``_over_mesh``), or
  ``blockwise_attention`` (same math, pure lax) so the CPU oracle tests in
  `tests/` exercise identical semantics; ``interpret=True`` runs the real
  kernels through the Pallas interpreter for parity testing without TPU
  hardware.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import _NEG, _round_up, kernel_impl

__all__ = ["flash_attention", "flash_attention_lse", "SAVED_NAMES"]

# checkpoint_names of the forward's two results, [B, H, T, D] and
# [B, H, T, 1]: with both kept, nothing of a second forward needs the kernel
SAVED_NAMES = ("flash_o", "flash_lse")


# ---------------------------------------------------------------------------
# the block schedule: how big a tile is, which tiles are visited
# ---------------------------------------------------------------------------

# tile sides a long sequence may take, largest first.  On the v5e at 4 x 16
# heads x 2048 x 128, bfloat16, causal, the top rung won or tied every kernel
# (PERF.md, PR 27): the per-step cost of carrying m, l and the accumulator
# falls with the tile's width faster than the diagonal's waste grows.
_LADDER = (1024, 512, 256, 128)
# scoped VMEM every call asks for (the compiler's default is 16 MiB of the
# v5e's 128 MiB); a tile's reckoned working set stays under it
_VMEM_LIMIT = 32 * 1024 * 1024


def _padded(T):
    """Length a sequence of ``T`` runs at: sub-tile sequences one tile of
    ``_round_up(T, 8)``, longer ones the next multiple of 128."""
    return _round_up(T, 8) if T <= 128 else _round_up(T, 128)


def _working_set(kernel, block_q, block_k, D, itemsize, Dv=None):
    """Reckoned VMEM bytes of one grid step: the operand and result tiles
    twice (the pipeline double-buffers them), the float32 score-sized tiles
    and their casts, the scratch accumulators.  A last dimension occupies
    whole rows of 128 lanes.  ``Dv`` is the width of ``v``, ``o``, ``do``
    and ``dv`` where it is not ``D``."""
    lanes = _round_up(D, 128)
    lanes_v = lanes if Dv is None else _round_up(Dv, 128)
    q_tile = block_q * lanes * itemsize        # q, dq
    o_tile = block_q * lanes_v * itemsize      # o, do
    k_tile = block_k * lanes * itemsize        # k, dk
    v_tile = block_k * lanes_v * itemsize      # v, dv
    row = block_q * 128 * 4                    # lse / delta / m / l: (bq, 1)
    score = block_q * block_k * 4
    cast = block_q * block_k * itemsize
    if kernel == "fwd":                        # q k v -> o lse; acc m l; s p
        return (2 * (q_tile + o_tile + k_tile + v_tile + row)
                + block_q * lanes_v * 4 + 2 * row + 2 * score + cast)
    if kernel == "dq":                         # q k v do lse delta -> dq
        return (2 * (2 * q_tile + o_tile + k_tile + v_tile + 2 * row)
                + block_q * lanes * 4 + 3 * score + cast)
    # dkv: q k v do lse delta -> dk dv; two accumulators; sT/pT dpT dsT
    return (2 * (q_tile + o_tile + 2 * k_tile + 2 * v_tile + 2 * row)
            + block_k * (lanes + lanes_v) * 4 + 3 * score + 2 * cast)


def _choose_tiles(Tq, Tk, D, itemsize, Dv=None):
    """``((block_q, block_k) of fwd, of dq, of dkv)`` from what the call can
    see.  Each side is the largest rung of the ladder that divides the
    padded length (so nothing pads further than ``_padded``); while the
    kernel's working set is over ``_VMEM_LIMIT`` the larger side steps down
    a rung."""
    def rungs(T_p):
        if T_p <= 128:
            return [T_p]
        return [r for r in _LADDER if T_p % r == 0]

    out = []
    for kernel in ("fwd", "dq", "dkv"):
        qs, ks = rungs(_padded(Tq)), rungs(_padded(Tk))
        while (_working_set(kernel, qs[0], ks[0], D, itemsize, Dv)
               > _VMEM_LIMIT
               and (len(qs) > 1 or len(ks) > 1)):
            q_steps = len(qs) > 1 and (qs[0] >= ks[0] or len(ks) == 1)
            (qs if q_steps else ks).pop(0)
        out.append((qs[0], ks[0]))
    return tuple(out)


def _row_map(b, h, i, j):
    """Block index of an operand tiled along the grid's outer axis."""
    return (b, h, i, 0)


def _band_ends(i, block_q, block_k, n_inner, inner_is_k, window, xp=jnp):
    """First and last inner tile that the band ``0 <= q - k < window`` of
    outer tile ``i`` touches, both included.  With k inside: from the tile
    that holds the first row's oldest key to the one that holds the last
    row's own position.  With q inside: from the tile that holds the first
    column's own position to the one that holds the last query that still
    sees the last column, or the sequence's last.  ``xp`` is ``numpy`` for
    every outer tile at once at trace time, ``jnp`` inside an index map or
    a kernel."""
    if inner_is_k:
        return (xp.maximum(i * block_q - window + 1, 0) // block_k,
                (i * block_q + block_q - 1) // block_k)
    return ((i * block_k) // block_q,
            xp.minimum((i * block_k + block_k + window - 2) // block_q,
                       n_inner - 1))


def _inner_axis(block_q, block_k, n_outer, n_inner, inner_is_k, window):
    """``(steps, first)`` of the grid's inner (sequential) axis.  With no
    window the axis is as long as the sequence and step ``j`` is tile ``j``:
    ``first`` is None.  With one it is as long as the longest run of inner
    tiles that any outer tile's band touches (a Python integer, never more
    than ``n_inner``: 5 of 16 at T = 16,384, W = 4,096, tiles of 1,024), and
    ``first(i)`` is the inner tile of outer tile ``i``'s step 0.  With k
    inside the run starts at the band's far edge, and an outer tile whose
    run is shorter (the sequence's start cuts its band) ends above the
    diagonal; with q inside it ends at the band's far edge or the sequence's
    last tile, and a shorter run starts above the diagonal (at a tile
    index that may be negative): either way ``_visit`` skips those steps by
    the conditions it has."""
    if window is None:
        return n_inner, None
    lo, hi = _band_ends(np.arange(n_outer), block_q, block_k, n_inner,
                        inner_is_k, window, np)
    steps = int((hi - lo + 1).max())

    def first(i):
        lo, hi = _band_ends(i, block_q, block_k, n_inner, inner_is_k, window)
        return lo if inner_is_k else hi - (steps - 1)
    return steps, first


def _inner_tile(first):
    """``(outer tile, inner tile, inner step, inner steps)`` of a kernel's
    grid step; tile and step are one where the call has no window."""
    i, j, n = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    return i, (j if first is None else first(i) + j), j, n


def _inner_map(causal, block_q, block_k, n_inner, inner_is_k, window=None,
               first=None):
    """Block index of an operand tiled along the grid's inner (reduction)
    axis.  Under ``causal`` a step that ``_visit`` skips keeps the index of
    the nearest tile that is needed, so the pipeline sees an unchanged block
    and issues no DMA: with k inside, the last k-tile the q-tile needs (the
    one holding its last row); with q inside, the first q-tile the k-tile
    needs (the one holding its first column; a k-tile past every query
    needs none and keeps the last).  With ``window`` step ``j`` is the tile
    ``first(i) + j`` (``_inner_axis``), held between the two ends of the
    outer tile's band (``_band_ends``)."""
    def index_map(b, h, i, j):
        if window is not None:
            lo, hi = _band_ends(i, block_q, block_k, n_inner, inner_is_k,
                                window)
            j = jnp.minimum(jnp.maximum(first(i) + j, lo), hi)
        elif causal and inner_is_k:
            j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
        elif causal:
            j = jnp.maximum(j, jnp.minimum((i * block_k) // block_q,
                                           n_inner - 1))
        return (b, h, j, 0)
    return index_map


def _note_tiles(kernel, block_q, block_k, nq, nk, causal, window=None):
    """Trace-time telemetry: which tile each kernel was built with, how long
    its inner grid axis is, and the share of the grid's tiles (and of its
    steps) the last traced call visits."""
    from ... import telemetry as _telemetry
    reg = _telemetry.registry()
    reg.counter("pallas.flash.tile.%s.%dx%d"
                % (kernel, block_q, block_k)).inc()
    run = nq * nk
    if causal:                                 # _visit's own conditions
        run = sum(ki * block_k <= qi * block_q + block_q - 1
                  for qi in range(nq) for ki in range(nk))
    reg.gauge("pallas.flash.causal_tiles_run_share").set(run / (nq * nk))
    if window is not None:
        reg.counter("pallas.flash.window.%s.%d" % (kernel, window)).inc()
        run = sum(ki * block_k <= qi * block_q + block_q - 1
                  and ki * block_k + block_k - 1 + window > qi * block_q
                  for qi in range(nq) for ki in range(nk))
        reg.gauge("pallas.flash.band_tiles_run_share").set(run / (nq * nk))
    n_outer, n_inner = (nk, nq) if kernel == "dkv" else (nq, nk)
    steps, _ = _inner_axis(block_q, block_k, n_outer, n_inner,
                           kernel != "dkv", window)
    reg.counter("pallas.flash.inner_steps.%s.%dof%d"
                % (kernel, steps, n_inner)).inc()
    reg.gauge("pallas.flash.grid_steps_run_share").set(
        run / (n_outer * steps))


def _visit(step, q0, k0, block_q, block_k, causal, kv_len, Tk, window=None):
    """Run ``step(masked)`` for the tile whose first row is ``q0`` and first
    column ``k0``: not at all where it lies wholly above the causal
    diagonal (or, with ``window``, wholly behind the band ``0 <= q - k <
    window``), masked where the diagonal (or the band's far edge) crosses
    it or it holds key padding, bare where it lies wholly inside."""
    pad = None if kv_len == Tk else k0 + block_k > kv_len
    if causal:
        run = k0 <= q0 + block_q - 1
        need = k0 + block_k - 1 > q0
        if window is not None:
            run = run & (k0 + block_k - 1 + window > q0)
            need = need | (k0 + window <= q0 + block_q - 1)
        if pad is not None:
            need = need | pad
        pl.when(run & need)(lambda: step(True))
        pl.when(run & jnp.logical_not(need))(lambda: step(False))
    elif pad is None:
        step(False)
    else:
        pl.when(pad)(lambda: step(True))
        pl.when(jnp.logical_not(pad))(lambda: step(False))


def _mask(s, q0, k0, causal, kv_len, k_axis, window=None):
    """``_NEG`` where a key is padding, (``causal``) after its query or
    (``window``) that many positions or more before it; ``k_axis`` is the
    axis of ``s`` that runs over keys."""
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, k_axis)
    valid = k_pos < kv_len
    if causal:
        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - k_axis)
        valid = valid & (q_pos >= k_pos)
        if window is not None:
            valid = valid & (q_pos - k_pos < window)
    return jnp.where(valid, s, _NEG)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))                                     # a . b^T
_NN = ((1,), (0,))                                     # a . b


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                scale, causal, block_q, block_k, kv_len, Tk, window, first):
    qi, ki, j, n = _inner_tile(first)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def step(masked):
        q = q_ref[0, 0]                                # (bq, D)
        k = k_ref[0, 0]                                # (bk, D)
        v = v_ref[0, 0]
        s = _dot(q, k, _NT) * scale                    # (bq, bk) f32
        if masked:
            s = _mask(s, qi * block_q, ki * block_k, causal, kv_len, 1, window)
        m_prev = m_ref[:, :1]                          # (bq, 1)
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + _dot(p.astype(v.dtype), v, _NN)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    _visit(step, qi * block_q, ki * block_k, block_q, block_k, causal,
           kv_len, Tk, window)

    @pl.when(j == n - 1)
    def _():
        l = l_ref[:, :1]
        # fully-masked rows (padding) have l == 0; emit 0 not nan
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:, :1] + jnp.log(safe)


def _name(kernel, window):
    """A call's name in the compiled program and the device trace."""
    return kernel if window is None else kernel + "_window"


def _fwd(q, k, v, causal, scale, tiles, kv_len, interpret, window=None):
    B, H, Tq, D = q.shape
    Tk, Dv = k.shape[2], v.shape[3]
    block_q, block_k = tiles[0]
    nq, nk = Tq // block_q, Tk // block_k
    _note_tiles("fwd", block_q, block_k, nq, nk, causal, window)
    steps, first = _inner_axis(block_q, block_k, nq, nk, True, window)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               kv_len=kv_len, Tk=Tk, window=window,
                               first=first)

    q_map = _row_map
    k_map = _inner_map(causal, block_q, block_k, nk, True, window, first)
    call = pl.pallas_call(
        kernel,
        name=_name("flash_fwd", window),
        grid=(B, H, nq, steps),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, block_k, D), k_map),
            pl.BlockSpec((1, 1, block_k, Dv), k_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, Dv), q_map),
            pl.BlockSpec((1, 1, block_q, 1), q_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, Tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * B * H * Tq * Tk * (D + Dv),
            bytes_accessed=2 * (B * H * ((Tq + Tk) * D + Tk * Dv)),
            transcendentals=B * H * Tq * Tk),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )
    with jax.named_scope("flash_fwd"):
        o, lse = call(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale, causal, block_q, block_k, kv_len, Tk, window,
               first):
    qi, ki, j, n = _inner_tile(first)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def step(masked):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        s = _dot(q, k, _NT) * scale
        if masked:
            s = _mask(s, qi * block_q, ki * block_k, causal, kv_len, 1, window)
        p = jnp.exp(s - lse_ref[0, 0])                 # lse: (bq, 1)
        dp = _dot(do, v, _NT)
        ds = p * (dp - delta_ref[0, 0]) * scale
        acc_ref[:] += _dot(ds.astype(k.dtype), k, _NN)

    _visit(step, qi * block_q, ki * block_k, block_q, block_k, causal,
           kv_len, Tk, window)

    @pl.when(j == n - 1)
    def _():
        dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                scale, causal, block_q, block_k, kv_len, Tk, window, first):
    ki, qi, j, n = _inner_tile(first)

    @pl.when(j == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def step(masked):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = jnp.transpose(lse_ref[0, 0])             # (1, bq)
        delta = jnp.transpose(delta_ref[0, 0])
        sT = _dot(k, q, _NT) * scale                   # transposed: (bk, bq)
        if masked:
            sT = _mask(sT, qi * block_q, ki * block_k, causal, kv_len, 0,
                       window)
        pT = jnp.exp(sT - lse)
        dv_acc[:] += _dot(pT.astype(do.dtype), do, _NN)
        dpT = _dot(v, do, _NT)
        dsT = pT * (dpT - delta) * scale
        dk_acc[:] += _dot(dsT.astype(q.dtype), q, _NN)

    _visit(step, qi * block_q, ki * block_k, block_q, block_k, causal,
           kv_len, Tk, window)

    @pl.when(j == n - 1)
    def _():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, causal, scale, tiles, kv_len, interpret,
         window=None, dlse=None):
    B, H, Tq, D = q.shape
    Tk, Dv = k.shape[2], v.shape[3]
    # delta_i = rowsum(do_i * o_i) — cheap elementwise, XLA fuses it
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)
    if dlse is not None:
        # lse is also an output: d lse_i / d s_ij = p_ij, so the lse
        # cotangent enters as ds_ij += p_ij * dlse_i — algebraically
        # identical to subtracting dlse from delta in ds = p*(dp - delta),
        # which reuses both kernels unchanged.
        delta = delta - dlse.astype(jnp.float32)

    block_q, block_k = tiles[1]
    nq, nk = Tq // block_q, Tk // block_k
    _note_tiles("dq", block_q, block_k, nq, nk, causal, window)
    steps, first = _inner_axis(block_q, block_k, nq, nk, True, window)

    q_map = _row_map
    k_map = _inner_map(causal, block_q, block_k, nk, True, window, first)
    qspec = pl.BlockSpec((1, 1, block_q, D), q_map)
    kspec = pl.BlockSpec((1, 1, block_k, D), k_map)
    vspec = pl.BlockSpec((1, 1, block_k, Dv), k_map)
    dospec = pl.BlockSpec((1, 1, block_q, Dv), q_map)
    rowq = pl.BlockSpec((1, 1, block_q, 1), q_map)
    dq_call = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, kv_len=kv_len,
                          Tk=Tk, window=window, first=first),
        name=_name("flash_dq", window),
        grid=(B, H, nq, steps),
        in_specs=[qspec, kspec, vspec, dospec, rowq, rowq],
        out_specs=[qspec],
        out_shape=[jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * B * H * Tq * Tk * (2 * D + Dv),
            bytes_accessed=2 * B * H * (Tq + Tk) * (D + Dv),
            transcendentals=B * H * Tq * Tk),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )
    with jax.named_scope("flash_dq"):
        dq = dq_call(q, k, v, do, lse, delta)[0]

    # grid transposed: outer k blocks, inner (sequential) q blocks
    block_q, block_k = tiles[2]
    nq, nk = Tq // block_q, Tk // block_k
    _note_tiles("dkv", block_q, block_k, nq, nk, causal, window)
    steps, first = _inner_axis(block_q, block_k, nk, nq, False, window)

    q_map2 = _inner_map(causal, block_q, block_k, nq, False, window, first)
    k_map2 = _row_map
    qspec2 = pl.BlockSpec((1, 1, block_q, D), q_map2)
    kspec2 = pl.BlockSpec((1, 1, block_k, D), k_map2)
    vspec2 = pl.BlockSpec((1, 1, block_k, Dv), k_map2)
    dospec2 = pl.BlockSpec((1, 1, block_q, Dv), q_map2)
    rowq2 = pl.BlockSpec((1, 1, block_q, 1), q_map2)
    dkv_call = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, kv_len=kv_len,
                          Tk=Tk, window=window, first=first),
        name=_name("flash_dkv", window),
        grid=(B, H, nk, steps),
        in_specs=[qspec2, kspec2, vspec2, dospec2, rowq2, rowq2],
        out_specs=[kspec2, vspec2],
        out_shape=[jax.ShapeDtypeStruct((B, H, Tk, D), k.dtype),
                   jax.ShapeDtypeStruct((B, H, Tk, Dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, Dv), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=4 * B * H * Tq * Tk * (D + Dv),
            bytes_accessed=2 * B * H * (Tq + 2 * Tk) * (D + Dv),
            transcendentals=B * H * Tq * Tk),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )
    with jax.named_scope("flash_dkv"):
        dk, dv = dkv_call(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrappers (operate on [B, H, T, D])
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, scale, tiles, kv_len, interpret, window):
    o, _ = _fwd(q, k, v, causal, scale, tiles, kv_len, interpret, window)
    return o


def _named_fwd(q, k, v, causal, scale, tiles, kv_len, interpret, window):
    """The forward of both VJP rules, its results named (``SAVED_NAMES``).
    ``lse`` is named as the kernel writes it, [B, H, T, 1]: a row of 128
    lanes a value in HBM (67 MB at 4 x 16 x 2048 for 0.5 MB of values).
    Named as [B, H, T] it is kept small and costs two copies a layer, 0.8 %
    of the Pythia cell's step where the room was not needed (PERF.md §6,
    PR 31)."""
    o, lse = _fwd(q, k, v, causal, scale, tiles, kv_len, interpret, window)
    return (checkpoint_name(o, SAVED_NAMES[0]),
            checkpoint_name(lse, SAVED_NAMES[1]))


def _flash_fwd(q, k, v, causal, scale, tiles, kv_len, interpret, window):
    o, lse = _named_fwd(q, k, v, causal, scale, tiles, kv_len, interpret,
                        window)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, tiles, kv_len, interpret, window, res, do):
    q, k, v, o, lse = res
    return _bwd(q, k, v, o, lse, do, causal, scale, tiles, kv_len, interpret,
                window)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_lse(q, k, v, causal, scale, tiles, kv_len, interpret, window):
    return _fwd(q, k, v, causal, scale, tiles, kv_len, interpret, window)


def _flash_lse_fwd(q, k, v, causal, scale, tiles, kv_len, interpret, window):
    o, lse = _named_fwd(q, k, v, causal, scale, tiles, kv_len, interpret,
                        window)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd(causal, scale, tiles, kv_len, interpret, window, res, ct):
    q, k, v, o, lse = res
    do, dlse = ct
    return _bwd(q, k, v, o, lse, do, causal, scale, tiles, kv_len, interpret,
                window, dlse=dlse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _attend(q, k, v, causal, scale, block_q, block_k, interpret, per_device,
            with_lse, window=None):
    """What both entry points share: the choice of implementation, the
    tiles, the [B, H, T, D] layout and the padding to whole tiles.  Returns
    ``(o, lse)`` with ``lse`` [B, H, T] or None."""
    B, T, H, D = q.shape
    Tk, Dv = k.shape[1], v.shape[3]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if window is not None:
        assert causal and T == Tk and window >= 1, \
            "a window is over a causal self-attention's own past"
        if window >= Tk:                       # the whole causal prefix
            window = None
    if interpret is None:
        # only the form without ``lse`` has a wrapper for a mesh
        impl = kernel_impl("flash_attention", sharded=not with_lse,
                           per_device=per_device)
        if impl == "sharded":
            return _over_mesh(q, k, v, causal, scale, block_q, block_k,
                              window), None
        if impl == "fallback":
            from ...parallel.ring_attention import blockwise_attention
            out = blockwise_attention(q, k, v, causal=causal, scale=scale,
                                      return_lse=with_lse, window=window)
            return out if with_lse else (out, None)
        interpret = impl == "interpret"

    tiles = tuple((block_q or bq, block_k or bk) for bq, bk in
                  _choose_tiles(T, Tk, D, jnp.dtype(q.dtype).itemsize,
                                None if Dv == D else Dv))
    # every kernel's tile divides the largest (rungs of one ladder)
    pq = _round_up(T, max(bq for bq, _ in tiles)) - T
    pk = _round_up(Tk, max(bk for _, bk in tiles)) - Tk
    if window is not None:
        # one padded length: a band's run of tiles ends inside the sequence
        pq = pk = max(pq, pk)

    def heads_first(x, pad):                           # -> [B, H, T + pad, D]
        x = x.transpose(0, 2, 1, 3)
        return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x

    qt, kt, vt = heads_first(q, pq), heads_first(k, pk), heads_first(v, pk)
    if with_lse:
        o, lse = _flash_lse(qt, kt, vt, causal, scale, tiles, Tk, interpret,
                            window)
        lse = lse[:, :, :T, 0]
    else:
        o, lse = _flash(qt, kt, vt, causal, scale, tiles, Tk, interpret,
                        window), None
    return o[:, :, :T].transpose(0, 2, 1, 3), lse


def _over_mesh(q, k, v, causal, scale, block_q, block_k, window=None):
    """The kernels under an active mesh, q/k/v [B, T, H, D] with the batch
    possibly sharded on ``dp`` and the heads on ``tp``.

    GSPMD cannot partition a custom call, so the kernel is wrapped in
    ``shard_map`` over the batch/head axes (attention is independent per
    batch element and head; sequence stays local, and with it a window —
    the sequence-sharded case is `parallel.ring_attention`)."""
    from ...parallel.mesh import current_mesh
    mesh = current_mesh()
    # inside the body each device holds its own shard: the kernel, forced
    local = functools.partial(flash_attention, causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k,
                              interpret=False, window=window)
    b = "dp" if mesh.size("dp") > 1 else None
    h = "tp" if mesh.size("tp") > 1 else None
    if b is None and h is None:
        return local(q, k, v)
    if (b is not None and q.shape[0] % mesh.size("dp")) or \
            (h is not None and q.shape[2] % mesh.size("tp")):
        # shard_map needs exact divisibility; under a mesh the raw pallas
        # call is unpartitionable by GSPMD, so fall back to the blockwise
        # lax path (which GSPMD shards/replicates freely)
        from ...parallel.ring_attention import blockwise_attention
        return blockwise_attention(q, k, v, causal=causal, scale=scale,
                                   window=window)
    from ...parallel.collectives import shard_map
    from jax.sharding import PartitionSpec as P
    spec = P(b, None, h, None)
    return shard_map(local, mesh=mesh.mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def flash_attention(q, k, v, causal=True, scale=None, block_q=None,
                    block_k=None, interpret=None, window=None):
    """Flash attention over q, k [B, T, H, D] and v [B, T, H, Dv] (``Dv``
    is ``D`` unless the values have a width of their own); the result is
    [B, T, H, Dv].  ``scale`` defaults to ``D ** -0.5``.  ``window`` (with
    ``causal``): query ``t`` sees the keys ``0 <= t - j < window`` alone;
    one that reaches over the whole sequence is the causal kernel.

    With ``interpret=None`` the implementation is ``common.kernel_impl``'s
    answer: the Pallas kernels above, the same inside a ``shard_map`` under
    a mesh, or the numerically-identical lax ``blockwise_attention``.
    ``interpret=True`` forces the kernels through the Pallas interpreter
    (CPU parity tests).  Differentiable via custom VJP (Pallas backward
    kernels).  ``block_q`` / ``block_k`` override the tiles
    ``_choose_tiles`` takes from the shape.
    """
    return _attend(q, k, v, causal, scale, block_q, block_k, interpret,
                   per_device=False, with_lse=False, window=window)[0]


def flash_attention_lse(q, k, v, causal=True, scale=None, block_q=None,
                        block_k=None, interpret=None, per_device=False):
    """Flash attention returning ``(o, lse)``.

    Same [B, T, H, D] API as :func:`flash_attention`, plus the per-row
    logsumexp [B, H, T] of the scaled masked scores (fully-masked rows get
    the ``-1e30`` sentinel).  This is the block kernel for flash-decoding
    style merges of normalized partials over disjoint key sets —
    `parallel.ring_attention(use_pallas=True)` combines one such call per
    ring step.  Differentiable in both outputs via custom VJP: the ``lse``
    cotangent folds into the ``delta`` operand of the same Pallas backward
    kernels (``ds += p * dlse``), so the merged-partials form trains
    end-to-end.  Under a mesh, outside a ``shard_map`` body, it is the lax
    blockwise kernel.
    """
    return _attend(q, k, v, causal, scale, block_q, block_k, interpret,
                   per_device=per_device, with_lse=True)
