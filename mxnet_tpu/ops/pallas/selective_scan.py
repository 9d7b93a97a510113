"""Selective scan (the Mamba-1 state-space recurrence) as a Pallas TPU kernel.

For every channel ``d`` and state ``n``, over time::

    h_t[d, n] = exp(delta_t[d] * A[d, n]) * h_{t-1}[d, n]
                + delta_t[d] * B_t[n] * u_t[d],            h_{-1} = 0
    y_t[d]    = sum_n C_t[n] * h_t[d, n] + D[d] * u_t[d]
    out_t[d]  = y_t[d] * silu(z_t[d])

The recurrence is sequential in time and elementwise in ``(d, n)``: no
matrix product, so it runs on the vector and transcendental units.  Design:

* grid ``(batch, Di / d_block, T / chunk)``, time last and ``arbitrary``
  (sequential): the ``[N, d_block]`` float32 state lives in VMEM scratch and
  is carried from chunk to chunk; channels sit on the lanes, states on the
  sublanes, so a time step is dense ``[N, d_block]`` tiles;
* a chunk is worked in three phases.  ``B``'s columns are first spread
  over the lanes, ``bb[t * N + n, :] = B_t[n]`` (one small scratch, a static
  lane slice and a strided store a column), so that the sequential loop
  reads a dense ``[N, 128]`` tile a step and never indexes a traced lane;
  the loop then does ``h = exp(delta_t A) h + delta_t u_t bb_t``, sixteen
  steps a loop body, and leaves every ``h_t`` in a scratch of ``chunk * N``
  rows a group of 128 lanes (``[d_block / 128, chunk * N, 128]``: a strided
  row access wants a 128-wide buffer); ``y`` is read back for all steps at
  once (strided rows, ``C``'s column ``n`` a static lane slice);
* the forward keeps the state at each chunk's start (``[batch, T / chunk, N,
  Di]`` float32); the backward runs the chunks in reverse, recomputes the
  chunk's states from that boundary, runs ``g_t = exp(delta_{t+1} A) g_{t+1}
  + C_t dy_t`` backwards into a second scratch (``C`` spread as ``B`` is),
  and reads every gradient off the two scratches in bulk.  ``dA`` and
  ``dD`` accumulate over time in their output blocks and are summed over
  the batch outside.  The forward's results carry ``checkpoint_name``s
  (``SAVED_NAMES``): a rematerialised layer that keeps them does not run
  the forward kernel a second time;
* ``chunk`` and ``d_block`` come from ``(T, Di, N)`` (``_choose_blocks``):
  the widest channel block of 1024 / 512 / 256 / 128 that divides ``Di``
  padded to 128 (the spread ``B`` tile is shared by all of a block's lane
  groups), then the longest chunk whose state scratch stays under 8 MiB;
* state, ``delta``, ``A``, ``exp`` and every accumulator are float32
  whatever the storage types; ``out`` and the gradients take their inputs'
  types;
* at trace time the counter ``pallas.ssm_scan.chunk.<fwd|bwd>.<chunk>x
  <d_block>`` records the schedule;
* off the TPU (and under a mesh, through the registry) the same chunked
  mathematics runs in ``lax``: a scan over chunks that keeps the boundary
  states, each chunk a scan over its steps recomputed in the backward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import _round_up, register_impl

__all__ = ["selective_scan", "selective_scan_lax"]

# what a ``jax.checkpoint`` policy may keep of the forward kernel's results
SAVED_NAMES = ("ssm_scan_out", "ssm_scan_bounds")
_F32 = jnp.float32
_LANES = 128
# rows of time a bulk phase handles at once, and steps of a sequential loop
# written out in one loop body (the TPU lowering unrolls a ``fori_loop``
# wholly or not at all): one packed bfloat16 tile
_ROWS = 16
# bytes of the [chunk * N, d_block] float32 state scratch (the backward holds
# two of them beside its double-buffered operand tiles)
_STATE_SCRATCH = 8 * 1024 * 1024
_VMEM_LIMIT = 48 * 1024 * 1024


def _choose_blocks(T, Di, N):
    """``(chunk, d_block)`` from the shape: the widest channel block that
    divides the padded ``Di``, then the longest chunk (a power of two, at
    least ``_ROWS``) whose state scratch fits and that does not pad a short
    sequence past its next multiple of ``_ROWS``."""
    di_p = _round_up(Di, 128)
    d_block = next(b for b in (1024, 512, 256, 128) if di_p % b == 0)
    chunk = 256
    while chunk > _ROWS and (chunk * _round_up(N, 8) * d_block * 4
                             > _STATE_SCRATCH
                             or chunk > _round_up(T, _ROWS)):
        chunk //= 2
    return chunk, d_block


def _note_blocks(kernel, chunk, d_block):
    from ... import telemetry as _telemetry
    _telemetry.registry().counter(
        "pallas.ssm_scan.chunk.%s.%dx%d" % (kernel, chunk, d_block)).inc()


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


# ---------------------------------------------------------------------------
# what both kernels share: a chunk's inputs, its sequential loop
#
# The state scratches are ``[d_block / 128, steps * N, 128]``: one plane a
# group of 128 lanes, because a strided row access wants a 128-wide buffer.
# Step ``t`` sits at rows ``[(t + slot) * N, (t + slot + 1) * N)``.
# ---------------------------------------------------------------------------

# The loop bodies below write ``*_ref`` arguments: inside a Pallas kernel a
# ref is a device buffer and the write is the program, not a trace-time side
# effect, so TS002 is disabled on exactly those bodies.

def _rows(ref, r0, g=None):
    """``_ROWS`` steps of a ``(1, chunk, width)`` block in float32: the whole
    width, or lane group ``g`` of it."""
    lanes = slice(None) if g is None else pl.ds(g * _LANES, _LANES)
    return ref[0, pl.ds(r0, _ROWS), lanes].astype(_F32)


def _state_rows(r0, n, N, slot=0):
    """Rows of state ``n`` over the ``_ROWS`` steps from ``r0``."""
    return pl.ds((r0 + slot) * N + n, _ROWS, stride=N)


def _spread_columns(wide_ref, narrow_ref, chunk, N):
    """``wide[t * N + n, :] = narrow[t, n]`` on every lane, for the chunk's
    steps: ``B`` (or ``C``) laid out as the sequential loops read it, a
    dense ``[N, 128]`` tile a step, so that no loop indexes a traced lane."""
    def body(tb, carry):  # mxlint: disable-block=TS002
        r0 = pl.multiple_of(tb * _ROWS, _ROWS)
        tile = _rows(narrow_ref, r0)
        for n in range(N):
            wide_ref[_state_rows(r0, n, N), :] = jnp.broadcast_to(
                tile[:, n:n + 1], (_ROWS, _LANES))
        return carry
    lax.fori_loop(0, chunk // _ROWS, body, 0)


def _lane_groups(x):
    """A ``(rows, d_block)`` value as a tuple of its 128-lane groups."""
    return tuple(x[:, g * _LANES:(g + 1) * _LANES]
                 for g in range(x.shape[1] // _LANES))


def _run_states(hs_ref, dt_ref, u_ref, bb_ref, a, h, chunk, N, slot):
    """The sequential part: ``h_t = exp(delta_t A) h_{t-1} + delta_t u_t
    B_t``, each ``h_t`` written to its slot of ``hs``.  ``a`` and ``h`` are
    tuples over the lane groups; returns the last state."""
    def steps(tg, h):  # mxlint: disable-block=TS002
        t0 = pl.multiple_of(tg * _ROWS, _ROWS)
        dt = _rows(dt_ref, t0)
        dts = _lane_groups(dt)
        dtus = _lane_groups(dt * _rows(u_ref, t0))
        for j in range(_ROWS):
            bt = bb_ref[pl.ds(pl.multiple_of((t0 + j) * N, N), N), :]
            row = pl.ds(pl.multiple_of((t0 + j + slot) * N, N), N)
            out = []
            for g, (ag, hg) in enumerate(zip(a, h)):
                hg = (jnp.exp(dts[g][j:j + 1] * ag) * hg
                      + dtus[g][j:j + 1] * bt)
                hs_ref[g, row, :] = hg
                out.append(hg)
            h = tuple(out)
        return h
    return lax.fori_loop(0, chunk // _ROWS, steps, h)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(u_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref,
                o_ref, hb_ref, h_ref, hs_ref, bb_ref, *, chunk, N):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[:] = jnp.zeros_like(h_ref)

    hb_ref[0, 0] = h_ref[:]                            # the chunk's start
    _spread_columns(bb_ref, b_ref, chunk, N)
    h = _run_states(hs_ref, dt_ref, u_ref, bb_ref, _lane_groups(a_ref[:]),
                    _lane_groups(h_ref[:]), chunk, N, 0)
    for g, hg in enumerate(h):
        h_ref[:, pl.ds(g * _LANES, _LANES)] = hg

    def emit(tb, carry):  # mxlint: disable-block=TS002
        r0 = pl.multiple_of(tb * _ROWS, _ROWS)
        ct = _rows(c_ref, r0)
        for g in range(hs_ref.shape[0]):
            lanes = pl.ds(g * _LANES, _LANES)
            y = d_ref[:, lanes] * _rows(u_ref, r0, g)
            for n in range(N):
                y = y + hs_ref[g, _state_rows(r0, n, N), :] * ct[:, n:n + 1]
            z = _rows(z_ref, r0, g)
            o_ref[0, pl.ds(r0, _ROWS), lanes] = (y * z * _sigmoid(z)).astype(
                o_ref.dtype)
        return carry
    lax.fori_loop(0, chunk // _ROWS, emit, 0)


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _fwd(u, dt, a, b, c, d, z, chunk, d_block, interpret):
    """Padded operands: u, dt, z [Bt, Tp, Dp]; a [N, Dp]; b, c [Bt, Tp, N];
    d [1, Dp].  Returns (out, the state at every chunk's start)."""
    Bt, Tp, Dp = u.shape
    N = a.shape[0]
    nc, nj = Tp // chunk, Dp // d_block
    _note_blocks("fwd", chunk, d_block)
    wide = pl.BlockSpec((1, chunk, d_block), lambda i, j, k: (i, k, j))
    narrow = pl.BlockSpec((1, chunk, N), lambda i, j, k: (i, k, 0))
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, N=N),
        name="ssm_scan_fwd",
        grid=(Bt, nj, nc),
        in_specs=[wide, wide, wide, narrow, narrow,
                  pl.BlockSpec((N, d_block), lambda i, j, k: (0, j)),
                  pl.BlockSpec((1, d_block), lambda i, j, k: (0, j))],
        out_specs=[wide,
                   pl.BlockSpec((1, 1, N, d_block),
                                lambda i, j, k: (i, k, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((Bt, Tp, Dp), u.dtype),
                   jax.ShapeDtypeStruct((Bt, nc, N, Dp), _F32)],
        scratch_shapes=[pltpu.VMEM((N, d_block), _F32),
                        pltpu.VMEM((d_block // _LANES, chunk * N, _LANES),
                                   _F32),
                        pltpu.VMEM((chunk * N, _LANES), _F32)],
        cost_estimate=pl.CostEstimate(
            flops=7 * Bt * Tp * Dp * N,
            bytes_accessed=Bt * Tp * Dp * (3 * u.dtype.itemsize + 4),
            transcendentals=Bt * Tp * Dp * (N + 1)),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )
    with jax.named_scope("ssm_scan_fwd"):
        out, bounds = call(u, dt, z, b, c, a, d)
    return out, bounds


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(u_ref, dt_ref, z_ref, do_ref, b_ref, c_ref, a_ref, d_ref,
                hb_ref,
                du_ref, ddt_ref, dz_ref, db_ref, dc_ref, da_ref, dd_ref,
                ga_ref, hs_ref, gs_ref, dy_ref, dacc_ref, bb_ref, cb_ref, *,
                chunk, N):
    k = pl.program_id(2)
    nk = pl.num_programs(2)
    groups = hs_ref.shape[0]

    @pl.when(k == 0)
    def _():                                           # the last chunk in time
        ga_ref[:] = jnp.zeros_like(ga_ref)
        dacc_ref[:] = jnp.zeros_like(dacc_ref)
        dd_ref[:] = jnp.zeros_like(dd_ref)

    a = _lane_groups(a_ref[:])
    # the chunk's states again, from the boundary the forward kept: step t
    # at slot t + 1, the boundary itself at slot 0 (it is step -1)
    h0 = _lane_groups(hb_ref[0, 0])
    for g in range(groups):
        hs_ref[g, pl.ds(0, N), :] = h0[g]
    _spread_columns(bb_ref, b_ref, chunk, N)
    _spread_columns(cb_ref, c_ref, chunk, N)
    _run_states(hs_ref, dt_ref, u_ref, bb_ref, a, h0, chunk, N, 1)

    lane = lax.broadcasted_iota(jnp.int32, (_ROWS, N), 1)

    def through_gate(tb, carry):  # mxlint: disable-block=TS002
        """dz, dC, dD, and dy kept for the reverse recurrence."""
        r0 = pl.multiple_of(tb * _ROWS, _ROWS)
        rows = pl.ds(r0, _ROWS)
        ct = _rows(c_ref, r0)
        dcs = [jnp.zeros((_ROWS, _LANES), _F32) for _ in range(N)]
        for g in range(groups):
            lanes = pl.ds(g * _LANES, _LANES)
            u = _rows(u_ref, r0, g)
            z = _rows(z_ref, r0, g)
            do = _rows(do_ref, r0, g)
            sz = _sigmoid(z)
            dy = do * z * sz
            dy_ref[rows, lanes] = dy
            y = d_ref[:, lanes] * u
            for n in range(N):
                hn = hs_ref[g, _state_rows(r0, n, N, 1), :]
                y = y + hn * ct[:, n:n + 1]
                dcs[n] = dcs[n] + dy * hn
            dz_ref[0, rows, lanes] = (
                do * y * sz * (1.0 + z * (1.0 - sz))).astype(dz_ref.dtype)
            dd_ref[0, :, lanes] += jnp.sum(dy * u, axis=0, keepdims=True)
        dc = jnp.zeros((_ROWS, N), _F32)
        for n in range(N):
            dc = jnp.where(lane == n,
                           jnp.sum(dcs[n], axis=1, keepdims=True), dc)
        dc_ref[0, 0, rows, :] = dc
        return carry
    lax.fori_loop(0, chunk // _ROWS, through_gate, 0)

    def back(ig, ga):  # mxlint: disable-block=TS002
        """g_t = exp(delta_{t+1} A) g_{t+1} + C_t dy_t, each written to its
        slot of ``gs``."""
        t0 = pl.multiple_of(chunk - (ig + 1) * _ROWS, _ROWS)
        dts = _lane_groups(_rows(dt_ref, t0))
        dys = _lane_groups(dy_ref[pl.ds(t0, _ROWS), :])
        for j in reversed(range(_ROWS)):
            row = pl.ds(pl.multiple_of((t0 + j) * N, N), N)
            ct = cb_ref[row, :]
            out = []
            for g in range(groups):
                gt = ga[g] + dys[g][j:j + 1] * ct
                gs_ref[g, row, :] = gt
                out.append(jnp.exp(dts[g][j:j + 1] * a[g]) * gt)
            ga = tuple(out)
        return ga
    ga = lax.fori_loop(0, chunk // _ROWS, back, _lane_groups(ga_ref[:]))
    for g in range(groups):
        ga_ref[:, pl.ds(g * _LANES, _LANES)] = ga[g]

    def gradients(tb, carry):  # mxlint: disable-block=TS002
        r0 = pl.multiple_of(tb * _ROWS, _ROWS)
        rows = pl.ds(r0, _ROWS)
        bt = _rows(b_ref, r0)
        dbs = [jnp.zeros((_ROWS, _LANES), _F32) for _ in range(N)]
        for g in range(groups):
            lanes = pl.ds(g * _LANES, _LANES)
            dt = _rows(dt_ref, r0, g)
            u = _rows(u_ref, r0, g)
            dtu = dt * u
            s = jnp.zeros((_ROWS, _LANES), _F32)       # sum_n g B
            w = jnp.zeros((_ROWS, _LANES), _F32)       # sum_n g a h_prev A
            for n in range(N):
                gn = gs_ref[g, _state_rows(r0, n, N), :]
                h_prev = hs_ref[g, _state_rows(r0, n, N, 0), :]
                an = a[g][n:n + 1, :]
                gah = gn * jnp.exp(dt * an) * h_prev
                w = w + gah * an
                s = s + gn * bt[:, n:n + 1]
                dacc_ref[g, pl.ds(n * _ROWS, _ROWS), :] += gah * dt
                dbs[n] = dbs[n] + gn * dtu
            ddt_ref[0, rows, lanes] = (w + s * u).astype(ddt_ref.dtype)
            du_ref[0, rows, lanes] = (
                s * dt + d_ref[:, lanes] * dy_ref[rows, lanes]).astype(
                    du_ref.dtype)
        db = jnp.zeros((_ROWS, N), _F32)
        for n in range(N):
            db = jnp.where(lane == n,
                           jnp.sum(dbs[n], axis=1, keepdims=True), db)
        db_ref[0, 0, rows, :] = db
        return carry
    lax.fori_loop(0, chunk // _ROWS, gradients, 0)

    @pl.when(k == nk - 1)
    def _():
        for g in range(groups):
            for n in range(N):
                da_ref[0, pl.ds(n, 1), pl.ds(g * _LANES, _LANES)] = jnp.sum(
                    dacc_ref[g, pl.ds(n * _ROWS, _ROWS), :], axis=0,
                    keepdims=True)


def _bwd(u, dt, a, b, c, d, z, bounds, do, chunk, d_block, interpret):
    Bt, Tp, Dp = u.shape
    N = a.shape[0]
    nc, nj, groups = Tp // chunk, Dp // d_block, d_block // _LANES
    _note_blocks("bwd", chunk, d_block)
    # time runs backwards: grid step k works chunk nc - 1 - k
    wide = pl.BlockSpec((1, chunk, d_block),
                        lambda i, j, k: (i, nc - 1 - k, j))
    narrow = pl.BlockSpec((1, chunk, N), lambda i, j, k: (i, nc - 1 - k, 0))
    part = pl.BlockSpec((1, 1, chunk, N),
                        lambda i, j, k: (i, j, nc - 1 - k, 0))
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, N=N),
        name="ssm_scan_bwd",
        grid=(Bt, nj, nc),
        in_specs=[wide, wide, wide, wide, narrow, narrow,
                  pl.BlockSpec((N, d_block), lambda i, j, k: (0, j)),
                  pl.BlockSpec((1, d_block), lambda i, j, k: (0, j)),
                  pl.BlockSpec((1, 1, N, d_block),
                               lambda i, j, k: (i, nc - 1 - k, 0, j))],
        out_specs=[wide, wide, wide, part, part,
                   pl.BlockSpec((1, N, d_block), lambda i, j, k: (i, 0, j)),
                   pl.BlockSpec((1, 1, d_block), lambda i, j, k: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((Bt, Tp, Dp), u.dtype),
                   jax.ShapeDtypeStruct((Bt, Tp, Dp), dt.dtype),
                   jax.ShapeDtypeStruct((Bt, Tp, Dp), z.dtype),
                   jax.ShapeDtypeStruct((Bt, nj, Tp, N), _F32),
                   jax.ShapeDtypeStruct((Bt, nj, Tp, N), _F32),
                   jax.ShapeDtypeStruct((Bt, N, Dp), _F32),
                   jax.ShapeDtypeStruct((Bt, 1, Dp), _F32)],
        scratch_shapes=[pltpu.VMEM((N, d_block), _F32),
                        pltpu.VMEM((groups, (chunk + 1) * N, _LANES), _F32),
                        pltpu.VMEM((groups, chunk * N, _LANES), _F32),
                        pltpu.VMEM((chunk, d_block), _F32),
                        pltpu.VMEM((groups, N * _ROWS, _LANES), _F32),
                        pltpu.VMEM((chunk * N, _LANES), _F32),
                        pltpu.VMEM((chunk * N, _LANES), _F32)],
        cost_estimate=pl.CostEstimate(
            flops=25 * Bt * Tp * Dp * N,
            bytes_accessed=Bt * Tp * Dp * (6 * u.dtype.itemsize + 8),
            transcendentals=Bt * Tp * Dp * (3 * N + 1)),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )
    with jax.named_scope("ssm_scan_bwd"):
        du, ddt, dz, db, dc, da, dd = call(u, dt, z, do, b, c, a, d, bounds)
    return (du, ddt, jnp.sum(da, axis=0), jnp.sum(db, axis=1).astype(b.dtype),
            jnp.sum(dc, axis=1).astype(c.dtype), jnp.sum(dd, axis=0), dz)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _scan(u, dt, a, b, c, d, z, chunk, d_block, interpret):
    return _fwd(u, dt, a, b, c, d, z, chunk, d_block, interpret)[0]


def _scan_fwd(u, dt, a, b, c, d, z, chunk, d_block, interpret):
    out, bounds = _fwd(u, dt, a, b, c, d, z, chunk, d_block, interpret)
    # named so that a rematerialised layer may keep them (SAVED_NAMES) and
    # not run this kernel a second time for the backward's sake
    out = checkpoint_name(out, SAVED_NAMES[0])
    bounds = checkpoint_name(bounds, SAVED_NAMES[1])
    return out, (u, dt, a, b, c, d, z, bounds)


def _scan_bwd(chunk, d_block, interpret, res, do):
    u, dt, a, b, c, d, z, bounds = res
    return _bwd(u, dt, a, b, c, d, z, bounds, do, chunk, d_block, interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


# ---------------------------------------------------------------------------
# the same chunked mathematics in lax
# ---------------------------------------------------------------------------

def selective_scan_lax(u, delta, A, B, C, D, z, chunk=None, d_block=None,
                       interpret=None):
    """The recurrence as a ``lax.scan`` over chunks whose body, a scan over
    the chunk's steps, is recomputed in the backward: the states kept are
    those at the chunk boundaries, as in the kernel."""
    Bt, T, Di = u.shape
    N = A.shape[1]
    if chunk is None:
        chunk = _choose_blocks(T, Di, N)[0]
    Tp = _round_up(T, chunk)

    def chunks(x):                                     # -> [nc, chunk, Bt, w]
        x = jnp.pad(x.astype(_F32), ((0, 0), (0, Tp - T), (0, 0)))
        return x.reshape(Bt, Tp // chunk, chunk, -1).transpose(1, 2, 0, 3)

    a = A.astype(_F32)

    def step(h, xs):
        ut, dt, bt, ct = xs                            # [Bt, Di] / [Bt, N]
        h = (jnp.exp(dt[..., None] * a) * h
             + (dt * ut)[..., None] * bt[:, None, :])
        return h, jnp.einsum("bdn,bn->bd", h, ct)

    @jax.checkpoint
    def one_chunk(h, xs):
        return lax.scan(step, h, xs)

    h0 = jnp.zeros((Bt, Di, N), _F32)
    _, y = lax.scan(one_chunk, h0,
                    (chunks(u), chunks(delta), chunks(B), chunks(C)))
    y = y.transpose(2, 0, 1, 3).reshape(Bt, Tp, Di)[:, :T]
    y = y + D.astype(_F32) * u.astype(_F32)
    zf = z.astype(_F32)
    return (y * zf * _sigmoid(zf)).astype(u.dtype)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def selective_scan(u, delta, A, B, C, D, z, chunk=None, d_block=None,
                   interpret=None):
    """Mamba-1 selective scan with the output gate.

    u, delta, z: [batch, T, Di]; A: [Di, N] (negative: ``-exp(A_log)``);
    B, C: [batch, T, N]; D: [Di].  Returns ``(C_t . h_t + D u_t) * silu(z_t)``
    as [batch, T, Di] in ``u``'s type.  On the TPU the Pallas kernels above
    (differentiable in every operand through their own backward kernel);
    elsewhere :func:`selective_scan_lax`.  ``interpret=True`` runs the
    kernels through the Pallas interpreter.  ``chunk`` / ``d_block``
    override what ``_choose_blocks`` takes from the shape.
    """
    if interpret is None:
        interpret = False
        if jax.default_backend() != "tpu":
            return selective_scan_lax(u, delta, A, B, C, D, z, chunk=chunk)
    Bt, T, Di = u.shape
    N = A.shape[1]
    auto = _choose_blocks(T, Di, N)
    chunk, d_block = chunk or auto[0], d_block or auto[1]
    # whole tiles: time to the chunk (delta 0 there, so the state stands
    # still), channels to the block, states to the sublane tile (A 0, B 0)
    pt = _round_up(T, chunk) - T
    pd = _round_up(Di, d_block) - Di
    pn = _round_up(N, 8) - N

    def wide(x, dtype=None):
        x = x if dtype is None else x.astype(dtype)
        return jnp.pad(x, ((0, 0), (0, pt), (0, pd))) if pt or pd else x

    def narrow(x):
        return jnp.pad(x, ((0, 0), (0, pt), (0, pn))) if pt or pn else x

    a = jnp.pad(A.astype(_F32).T, ((0, pn), (0, pd)))
    d = jnp.pad(D.astype(_F32), (0, pd)).reshape(1, Di + pd)
    out = _scan(wide(u), wide(delta, _F32), a, narrow(B), narrow(C), d,
                wide(z), chunk, d_block, interpret)
    return out[:, :T, :Di]


register_impl("selective_scan", pallas=selective_scan,
              fallback=selective_scan_lax)
