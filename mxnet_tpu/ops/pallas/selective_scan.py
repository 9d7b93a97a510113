"""Selective scan (the Mamba-1 state-space recurrence) as a Pallas TPU kernel.

For every channel ``d`` and state ``n``, over time::

    h_t[d, n] = exp(delta_t[d] * A[d, n]) * h_{t-1}[d, n]
                + delta_t[d] * B_t[n] * u_t[d],            h_{-1} = 0
    y_t[d]    = sum_n C_t[n] * h_t[d, n] + D[d] * u_t[d]
    out_t[d]  = y_t[d] * silu(z_t[d])

The recurrence is sequential in time and elementwise in ``(d, n)``: no
matrix product, so it runs on the vector and transcendental units, and on
the v5e the four vector slots of an instruction bundle bound every loop.
The schedule is written to keep vector operations, strided accesses and
cross-lane work off the state tiles.  Design:

* grid ``(batch, T / chunk, Di / d_block)``, both ``arbitrary``, the channel
  blocks innermost: the ``[N, d_block]`` float32 state of every channel
  block lives in VMEM scratch and is carried from chunk to chunk; channels
  sit on the lanes, states on the sublanes, so a time step is dense ``[N,
  d_block]`` tiles;
* once a chunk (at its first channel block) ``B`` and ``C`` are spread over
  the lanes, ``bb[t * (N + 4) + n, :] = B_t[n]``, so that a sequential loop
  reads a dense ``[N, 128]`` tile a step and never indexes a traced lane;
* **the pitch.**  VMEM rows lie in 32 banks.  A strided load or store whose
  pitch is a multiple of 16 rows is split in four by the compiler (two rows
  an instruction, ``vor``-ed together), of 8 in two; at ``4 mod 8`` rows it
  is one instruction.  Every scratch that is written a step at a time and
  read a state at a time (or the other way) therefore leaves 4 rows empty
  after each tile (``_PAD``); plain accesses do not mind where they start.
  A strided access issues once a cycle where three plain loads do;
* **forward, no stored states.**  The sequential loop does ``h =
  exp(delta_t A) h + delta_t u_t B_t``, sixteen steps a loop body,
  the step's rows of ``delta`` and ``delta u`` read by a load of stride 0
  (the load unit spreads a row over the sublanes for nothing).  While
  ``h_t`` is in registers it is multiplied by the spread ``C_t`` and its
  sublane tiles are added: eight rows a step go to a scratch (by strided
  stores, a row at a time), and a light bulk phase sums the eight, adds ``D
  u`` and gates.  A state tile makes no trip through VMEM;
* the forward keeps the state at every chunk's start (``[batch, T / chunk,
  N, Di]`` float32); both kernels work the same chunk;
* **backward, one trip a tile and scratch.**  It runs the chunks in reverse
  and re-makes the chunk's states from the kept one.  Into the first
  scratch goes the state *as it came into the step*, ``exp(delta_t A)
  h_{t-1}``: ``dA`` and ``ddelta`` are made of that, and ``h_t`` is that plus
  ``delta_t u_t B_t`` to the bit, so the backward needs neither a third
  ``exp`` nor the slot before.  ``dC_t = sum_d dy_t h_t`` is taken while
  ``h_t`` is in registers.  The reverse loop ``g_t = exp(delta_{t+1} A)
  g_{t+1} + C_t dy_t`` (``dy = do z sigmoid(z)`` formed sixteen steps at a
  time) writes ``g_t`` to the second scratch and takes ``dB_t = sum_d g_t
  delta_t u_t`` on the way.  One bulk phase then reads both scratches once,
  a state at a time, for what is summed over the states (``y`` for ``dz``,
  ``du``, ``ddelta``) and for ``dA``, whose sums over the chunk's steps are
  folded to eight rows a state and added at the chunk's end.  ``dB`` and
  ``dC`` leave states-first, a chunk's steps on the lanes of ``[N, 128]``
  tiles, and are summed over blocks outside; ``dA`` and ``dD`` are summed in
  output blocks that stay in VMEM for a whole batch entry (``[Di / d_block,
  N, d_block]``, one block index for all of the entry's grid steps), and
  over the batch outside.  The forward's results carry
  ``checkpoint_name``s (``SAVED_NAMES``): a rematerialised layer that keeps
  them does not run the forward kernel a second time;
* ``chunk`` and ``d_block`` come from ``(T, Di, N)`` (``_choose_blocks``):
  the widest channel block of 1024 / 512 / 256 / 128 that divides ``Di``
  padded to 128, then the longest chunk whose state scratch (``chunk * (N +
  4)`` rows of ``d_block``) stays under 12 MiB;
* state, ``delta``, ``A``, ``exp`` and every accumulator are float32
  whatever the storage types; ``out`` and the gradients take their inputs'
  types;
* at trace time the counter ``pallas.ssm_scan.chunk.<fwd|bwd>.<chunk>x
  <d_block>`` counts each kernel traced, by its grid step;
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

from .common import _round_up, kernel_impl

__all__ = ["selective_scan", "selective_scan_lax"]

# what a ``jax.checkpoint`` policy may keep of the forward kernel's results
SAVED_NAMES = ("ssm_scan_out", "ssm_scan_bounds")
_F32 = jnp.float32
_LANES = 128
# rows of time a bulk phase handles at once, and steps of a sequential loop
# written out in one loop body (the TPU lowering unrolls a ``fori_loop``
# wholly or not at all): one packed bfloat16 tile
_ROWS = 16
# rows left empty after a run of rows in a scratch that a strided access
# crosses: with a pitch of 4 mod 8 rows the eight rows of a strided load or
# store fall in eight different banks and it is one instruction; at a pitch
# of 16 rows the v5e's compiler splits it in four, at 8 or 24 in two
_PAD = 4
# bytes of a [chunk * (N + 4), d_block] float32 state scratch (the backward
# holds two of them beside its double-buffered operand tiles)
_STATE_SCRATCH = 12 * 1024 * 1024
_VMEM_LIMIT = 48 * 1024 * 1024


def _choose_blocks(T, Di, N):
    """``(chunk, d_block)`` from the shape: the widest channel block that
    divides the padded ``Di``, then the longest chunk (a power of two, at
    least ``_ROWS``) whose state scratch fits and that does not pad a short
    sequence past its next multiple of ``_ROWS``."""
    di_p = _round_up(Di, 128)
    d_block = next(b for b in (1024, 512, 256, 128) if di_p % b == 0)
    chunk = 256
    while chunk > _ROWS and (chunk * (_round_up(N, 8) + _PAD) * d_block * 4
                             > _STATE_SCRATCH
                             or chunk > _round_up(T, _ROWS)):
        chunk //= 2
    return chunk, d_block


def _check_chunk(chunk):
    """A chunk is whole loop bodies of ``_ROWS`` steps, and its ``dB`` and
    ``dC`` leave on the lanes of ``[N, 128]`` tiles: part of one tile, or
    whole tiles."""
    if chunk % _ROWS or (chunk > _LANES and chunk % _LANES):
        raise ValueError(
            "chunk must be a multiple of %d, and of %d where it is longer: "
            "got %d" % (_ROWS, _LANES, chunk))


def _note_blocks(kernel, chunk, d_block):
    from ... import telemetry as _telemetry
    _telemetry.registry().counter(
        "pallas.ssm_scan.chunk.%s.%dx%d" % (kernel, chunk, d_block)).inc()


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


# ---------------------------------------------------------------------------
# what both kernels share: a chunk's inputs, its sequential loop
#
# Two layouts of a quantity that has a ``[rows, 128]`` tile a step, both 128
# lanes wide (a strided row access wants a 128-wide buffer; where the
# quantity is a state, one plane a group of 128 lanes):
#
# * a step at a time: step ``t`` at rows ``[t * (rows + _PAD), ... + rows)``
#   (``_step_tile``), row ``r`` of sixteen steps a strided access
#   (``_steps_row``).  ``B`` and ``C`` spread over the lanes (written
#   strided, read plainly by the sequential loops) and the backward's two
#   state scratches (written plainly by the sequential loops, read strided
#   by the bulk phase: strided stores there raised the loops' spills and
#   cost more than they saved the bulk phase, 2.52 against 2.46 ms).
# * a row at a time: row ``r`` of the sixteen steps from ``r0`` at rows
#   ``[(r0 / 16 * rows + r) * (16 + _PAD), ... + 16)`` (``_row_steps``), a
#   step's tile a strided access (``_step_rows``).  The forward's partial
#   ``y`` (written strided by the sequential loop, read plainly) and the
#   second copy of the spread ``B`` and ``C`` that the backward's bulk phase
#   reads.
#
# A strided access issues once a cycle where three plain loads do.
# ---------------------------------------------------------------------------

# The loop bodies below write ``*_ref`` arguments: inside a Pallas kernel a
# ref is a device buffer and the write is the program, not a trace-time side
# effect, so TS002 is disabled on exactly those bodies.

def _rows(ref, r0, g=None):
    """``_ROWS`` steps of a ``(1, chunk, width)`` block in float32: the whole
    width, or lane group ``g`` of it."""
    return ref[0, pl.ds(r0, _ROWS), _lanes(g)].astype(_F32)


def _lanes(g):
    return slice(None) if g is None else pl.ds(g * _LANES, _LANES)


def _step_tile(t, rows):
    """Step ``t``'s tile in the layout a step at a time."""
    return pl.ds(t * (rows + _PAD), rows)


def _steps_row(r0, r, rows, steps=_ROWS):
    """Row ``r`` of the ``steps`` steps from ``r0`` in that layout."""
    return pl.ds(r0 * (rows + _PAD) + r, steps, stride=rows + _PAD)


def _row_steps(r0, r, rows):
    """Row ``r`` of the ``_ROWS`` steps from ``r0`` in the layout a row at a
    time."""
    return pl.ds((r0 // _ROWS * rows + r) * (_ROWS + _PAD), _ROWS)


def _step_rows(t0, j, rows):
    """Step ``t0 + j``'s tile in that layout (``t0`` a multiple of
    ``_ROWS``)."""
    return pl.ds(t0 // _ROWS * rows * (_ROWS + _PAD) + j, rows,
                 stride=_ROWS + _PAD)


def _by_row(steps, rows):
    """Rows of a scratch in the layout a row at a time."""
    return steps // _ROWS * rows * (_ROWS + _PAD)


def _spread_columns(wide_ref, narrow_ref, chunk, N, rows_ref=None):
    """``wide[t * pitch + n, :] = narrow[t, n]`` on every lane, for the
    chunk's steps: ``B`` (or ``C``) laid out as the sequential loops read it,
    a dense ``[N, 128]`` tile a step, so that no loop indexes a traced lane;
    and, where the bulk phase wants it too, a row at a time into
    ``rows_ref``.  One scratch serves all of a block's lane groups."""
    def body(tb, carry):  # mxlint: disable-block=TS002
        r0 = pl.multiple_of(tb * _ROWS, _ROWS)
        tile = _rows(narrow_ref, r0)
        for n in range(N):
            col = jnp.broadcast_to(tile[:, n:n + 1], (_ROWS, _LANES))
            wide_ref[_steps_row(r0, n, N), :] = col
            if rows_ref is not None:
                rows_ref[_row_steps(r0, n, N), :] = col
        return carry
    lax.fori_loop(0, chunk // _ROWS, body, 0)


def _lane_groups(x):
    """A ``(rows, d_block)`` value as a tuple of its 128-lane groups."""
    return tuple(x[:, g * _LANES:(g + 1) * _LANES]
                 for g in range(x.shape[1] // _LANES))


def _lay_rows(x_ref, t0, dt_ref, u_ref, z_ref=None, do_ref=None):
    """Sixteen steps' rows of ``delta``, ``delta u`` and (for the backward)
    ``dy = do z sigmoid(z)`` laid into the planes of ``x`` (a plane a
    quantity and lane group), so that a step of a sequential loop reads its
    row from a static offset."""
    for g in range(x_ref.shape[1]):
        dt = _rows(dt_ref, t0, g)
        x_ref[0, g] = dt
        x_ref[1, g] = dt * _rows(u_ref, t0, g)
        if z_ref is not None:
            z = _rows(z_ref, t0, g)
            x_ref[2, g] = _rows(do_ref, t0, g) * z * _sigmoid(z)


def _row(x_ref, plane, j, g, N, interpret):
    """Row ``j`` of a plane of ``x`` on ``N`` sublanes: a load of stride 0,
    which the load unit spreads over the sublanes for nothing (a one-row
    load and a broadcast take a vector operation a tile).  The interpreter
    cannot slice with stride 0 and broadcasts: the one place where
    ``interpret`` forks the kernels' code, so the tests off the chip never
    run this load.  ``tests/test_pallas_v5e_compile.py`` holds the compiled
    kernels to it (no broadcast in the sequential loops' bundles) and
    ``chip_smoke.py`` checks its values on the chip."""
    if interpret:
        return jnp.broadcast_to(x_ref[plane, g, pl.ds(j, 1), :], (N, _LANES))
    return x_ref[plane, g, pl.ds(j, N, stride=0), :]


def _run_states(x_ref, bb_ref, a, h, chunk, N, sixteen, interpret):
    """The sequential part: ``h_t = exp(delta_t A) h_{t-1} + delta_t u_t
    B_t`` over the chunk's steps, sixteen steps a loop body.
    ``a`` and ``h`` are tuples over the lane groups.
    ``sixteen(t0)`` fills ``x`` for the sixteen steps from ``t0`` and returns
    ``(keep, done)``: ``keep(j, h, decayed)`` is handed every new state, and
    the old one as it came into the step (``exp(delta_t A) h_{t-1}``), while
    they are in registers; ``done()`` closes the body.  Returns the last
    state."""
    def steps_of(tg, h):  # mxlint: disable-block=TS002
        t0 = pl.multiple_of(tg * _ROWS, _ROWS)
        keep, done = sixteen(t0)
        for j in range(_ROWS):
            bt = bb_ref[_step_tile(t0 + j, N), :]
            # exp(delta A) as a plain scan takes it: 2 ** (delta (A log2 e))
            # saves a multiply a tile but rounds otherwise, so every state
            # differs from a plain scan's in its last bits (PERF.md, PR 29)
            decayed = tuple(
                jnp.exp(_row(x_ref, 0, j, g, N, interpret) * ag) * hg
                for g, (ag, hg) in enumerate(zip(a, h)))
            h = tuple(hd + _row(x_ref, 1, j, g, N, interpret) * bt
                      for g, hd in enumerate(decayed))
            keep(j, h, decayed)
        done()
        return h
    return lax.fori_loop(0, chunk // _ROWS, steps_of, h)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(u_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref,
                o_ref, hb_ref, h_ref, ys_ref, x_ref, bb_ref, cb_ref, *,
                chunk, N, interpret):
    jb = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[jb] = jnp.zeros(h_ref.shape[1:], _F32)

    @pl.when(jb == 0)
    def _():                       # the chunk's B and C serve every block
        _spread_columns(bb_ref, b_ref, chunk, N)
        _spread_columns(cb_ref, c_ref, chunk, N)
    a = _lane_groups(a_ref[:])

    def sixteen(t0):
        _lay_rows(x_ref, t0, dt_ref, u_ref)

        def partial_y(j, h, _decayed):
            """``C_t h_t`` summed over the sublane tiles: eight rows a step,
            the sum over them left to the bulk phase."""
            ct = cb_ref[_step_tile(t0 + j, N), :]
            for g, hg in enumerate(h):
                p = hg * ct
                ys_ref[g, _step_rows(t0, j, 8), :] = sum(
                    p[i:i + 8] for i in range(0, N, 8))
        return partial_y, lambda: None

    hb_ref[0, 0] = h_ref[jb]                           # the chunk's start
    h = _run_states(x_ref, bb_ref, a, _lane_groups(h_ref[jb]), chunk, N,
                    sixteen, interpret)
    for g, hg in enumerate(h):
        h_ref[jb, :, _lanes(g)] = hg

    def emit(tb, carry):  # mxlint: disable-block=TS002
        r0 = pl.multiple_of(tb * _ROWS, _ROWS)
        for g in range(ys_ref.shape[0]):
            y = d_ref[:, _lanes(g)] * _rows(u_ref, r0, g)
            for r in range(8):
                y = y + ys_ref[g, _row_steps(r0, r, 8), :]
            z = _rows(z_ref, r0, g)
            o_ref[0, pl.ds(r0, _ROWS), _lanes(g)] = (
                y * z * _sigmoid(z)).astype(o_ref.dtype)
        return carry
    lax.fori_loop(0, chunk // _ROWS, emit, 0)


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _fwd(u, dt, a, b, c, d, z, chunk, d_block, interpret):
    """Padded operands: u, dt, z [Bt, Tp, Dp]; a [N, Dp]; b, c [Bt, Tp, N];
    d [1, Dp].  Returns (out, the state at every chunk's start)."""
    Bt, Tp, Dp = u.shape
    N = a.shape[0]
    nc, nj, groups = Tp // chunk, Dp // d_block, d_block // _LANES
    _note_blocks("fwd", chunk, d_block)
    # time, then the channel blocks: a chunk's B and C are spread once
    wide = pl.BlockSpec((1, chunk, d_block), lambda i, k, j: (i, k, j))
    narrow = pl.BlockSpec((1, chunk, N), lambda i, k, j: (i, k, 0))
    spread = pltpu.VMEM((chunk * (N + _PAD), _LANES), _F32)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, N=N,
                          interpret=interpret),
        name="ssm_scan_fwd",
        grid=(Bt, nc, nj),
        in_specs=[wide, wide, wide, narrow, narrow,
                  pl.BlockSpec((N, d_block), lambda i, k, j: (0, j)),
                  pl.BlockSpec((1, d_block), lambda i, k, j: (0, j))],
        out_specs=[wide,
                   pl.BlockSpec((1, 1, N, d_block),
                                lambda i, k, j: (i, k, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((Bt, Tp, Dp), u.dtype),
                   jax.ShapeDtypeStruct((Bt, nc, N, Dp), _F32)],
        scratch_shapes=[pltpu.VMEM((nj, N, d_block), _F32),
                        pltpu.VMEM((groups, _by_row(chunk, 8), _LANES), _F32),
                        pltpu.VMEM((2, groups, _ROWS, _LANES), _F32),
                        spread, spread],
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

class _Columns:
    """``dB`` or ``dC`` of a loop body's sixteen steps: each step hands a
    ``[N, 128]`` tile to be summed over its lanes, the sums are gathered on
    the lanes of one tile and leave the kernel states-first, a chunk's steps
    on the lanes of its ``[N, 128]`` tiles."""

    def __init__(self, ref, t0, N):
        self.ref, self.t0 = ref, t0
        self.lane = lax.broadcasted_iota(jnp.int32, (N, _LANES), 1)
        self.tile = ref[0, 0, t0 // _LANES]

    def put(self, j, tile):
        self.tile = jnp.where(self.lane == self.t0 % _LANES + j,
                              jnp.sum(tile, axis=1, keepdims=True),
                              self.tile)

    def store(self):
        self.ref[0, 0, self.t0 // _LANES] = self.tile


def _bwd_kernel(u_ref, dt_ref, z_ref, do_ref, b_ref, c_ref, a_ref, d_ref,
                hb_ref,
                du_ref, ddt_ref, dz_ref, db_ref, dc_ref, da_ref, dd_ref,
                ga_ref, hd_ref, gs_ref, x_ref, das_ref, bb_ref, cb_ref,
                bs_ref, cs_ref, *, chunk, N, interpret):
    k = pl.program_id(1)
    jb = pl.program_id(2)
    groups = hd_ref.shape[0]

    @pl.when(k == 0)
    def _():                                           # the last chunk in time
        ga_ref[jb] = jnp.zeros(ga_ref.shape[1:], _F32)
        da_ref[0, jb] = jnp.zeros(da_ref.shape[2:], _F32)
        dd_ref[0, jb] = jnp.zeros(dd_ref.shape[2:], _F32)

    @pl.when(jb == 0)
    def _():                       # the chunk's B and C serve every block
        _spread_columns(bb_ref, b_ref, chunk, N, bs_ref)
        _spread_columns(cb_ref, c_ref, chunk, N, cs_ref)
    a = _lane_groups(a_ref[:])

    def lay(t0):
        _lay_rows(x_ref, t0, dt_ref, u_ref, z_ref, do_ref)

    def row(plane, j, g):
        return _row(x_ref, plane, j, g, N, interpret)

    # the chunk's states again, from the one the forward kept at its start.
    # What goes to ``hd`` is the state as it came into the step, exp(delta_t
    # A) h_{t-1}: dA and ddelta are made of that, and h_t is it plus delta_t
    # u_t B_t, to the bit.  dC_t = sum_d dy_t h_t while h_t is there
    def sixteen(t0):
        lay(t0)
        dc = _Columns(dc_ref, t0, N)

        def keep_state(j, h, decayed):
            for g, hd in enumerate(decayed):
                hd_ref[g, _step_tile(t0 + j, N), :] = hd
            dc.put(j, sum(row(2, j, g) * hg for g, hg in enumerate(h)))
        return keep_state, dc.store
    _run_states(x_ref, bb_ref, a, _lane_groups(hb_ref[0, 0]), chunk, N,
                sixteen, interpret)

    def back(ig, ga):  # mxlint: disable-block=TS002
        """g_t = exp(delta_{t+1} A) g_{t+1} + C_t dy_t, each written to its
        slot of ``gs``; dB_t = sum_d g_t delta_t u_t while g_t is there."""
        t0 = pl.multiple_of(chunk - (ig + 1) * _ROWS, _ROWS)
        lay(t0)
        db = _Columns(db_ref, t0, N)
        for j in reversed(range(_ROWS)):
            ct = cb_ref[_step_tile(t0 + j, N), :]
            gt = tuple(ga[g] + row(2, j, g) * ct for g in range(groups))
            for g in range(groups):
                gs_ref[g, _step_tile(t0 + j, N), :] = gt[g]
            db.put(j, sum(row(1, j, g) * gt[g] for g in range(groups)))
            ga = tuple(jnp.exp(row(0, j, g) * a[g]) * gt[g]
                       for g in range(groups))
        db.store()
        return ga
    ga = lax.fori_loop(0, chunk // _ROWS, back, _lane_groups(ga_ref[jb]))
    for g in range(groups):
        ga_ref[jb, :, _lanes(g)] = ga[g]

    das_ref[:] = jnp.zeros_like(das_ref)

    def gradients(tb, carry):  # mxlint: disable-block=TS002
        """What is summed over the states, for sixteen steps from one read
        of their decayed state and their ``g``, a state at a time; and
        ``dA``."""
        r0 = pl.multiple_of(tb * _ROWS, _ROWS)
        rows = pl.ds(r0, _ROWS)
        for g in range(groups):
            lanes = _lanes(g)
            dt = _rows(dt_ref, r0, g)
            u = _rows(u_ref, r0, g)
            z = _rows(z_ref, r0, g)
            do = _rows(do_ref, r0, g)
            sz = _sigmoid(z)
            dy = do * z * sz
            dtu = dt * u
            y = d_ref[:, lanes] * u
            s = jnp.zeros((_ROWS, _LANES), _F32)       # sum_n g B
            w = jnp.zeros((_ROWS, _LANES), _F32)       # sum_n g a h_prev A
            for n in range(N):
                hd = hd_ref[g, _steps_row(r0, n, N), :]
                gn = gs_ref[g, _steps_row(r0, n, N), :]
                bn = bs_ref[_row_steps(r0, n, N), :]
                y = y + (hd + dtu * bn) * cs_ref[_row_steps(r0, n, N), :]
                s = s + gn * bn
                gah = gn * hd
                w = w + gah * a_ref[pl.ds(n, 1), lanes]
                da = gah * dt
                das_ref[g, _step_tile(n, 8), :] += da[:8] + da[8:]
            dz_ref[0, rows, lanes] = (
                do * y * sz * (1.0 + z * (1.0 - sz))).astype(dz_ref.dtype)
            ddt_ref[0, rows, lanes] = (w + s * u).astype(ddt_ref.dtype)
            du_ref[0, rows, lanes] = (
                s * dt + d_ref[:, lanes] * dy).astype(du_ref.dtype)
            dd_ref[0, jb, :, lanes] += jnp.sum(dy * u, axis=0,
                                               keepdims=True)
        return carry
    lax.fori_loop(0, chunk // _ROWS, gradients, 0)

    # the chunk's dA: the eight rows a state that the bulk phase left, row r
    # of every state one strided load
    for g in range(groups):
        da_ref[0, jb, :, _lanes(g)] += sum(
            das_ref[g, _steps_row(0, r, 8, N), :] for r in range(8))


def _time_major(x, chunk):
    """``dB`` / ``dC`` as the backward kernel leaves them, ``[Bt, Di /
    d_block, tiles, N, 128]`` with a chunk's steps on the lanes of its
    tiles (a chunk under 128 fills the first lanes of one tile, a longer one
    is whole tiles: ``_check_chunk``), summed over the channel blocks into
    ``[Bt, Tp, N]``."""
    x = jnp.sum(x, axis=1)[..., :min(chunk, _LANES)]
    return x.transpose(0, 1, 3, 2).reshape(x.shape[0], -1, x.shape[2])


def _channel_major(x):
    """``dA`` / ``dD`` as the backward kernel leaves them, ``[Bt, Di /
    d_block, rows, d_block]``, summed over the batch into ``[rows, Di]``."""
    x = jnp.sum(x, axis=0)
    return x.transpose(1, 0, 2).reshape(x.shape[1], -1)


def _bwd(u, dt, a, b, c, d, z, bounds, do, chunk, d_block, interpret):
    Bt, Tp, Dp = u.shape
    N = a.shape[0]
    nc, nj, groups = Tp // chunk, Dp // d_block, d_block // _LANES
    tiles = -(-chunk // _LANES)                        # [N, 128] a chunk
    _note_blocks("bwd", chunk, d_block)
    # time runs backwards: grid step k works chunk nc - 1 - k, for every
    # channel block in turn
    wide = pl.BlockSpec((1, chunk, d_block),
                        lambda i, k, j: (i, nc - 1 - k, j))
    narrow = pl.BlockSpec((1, chunk, N), lambda i, k, j: (i, nc - 1 - k, 0))
    part = pl.BlockSpec((1, 1, tiles, N, _LANES),
                        lambda i, k, j: (i, j, nc - 1 - k, 0, 0))
    states = pltpu.VMEM((groups, chunk * (N + _PAD), _LANES), _F32)
    spread = pltpu.VMEM((chunk * (N + _PAD), _LANES), _F32)
    by_row = pltpu.VMEM((_by_row(chunk, N), _LANES), _F32)
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, N=N,
                          interpret=interpret),
        name="ssm_scan_bwd",
        grid=(Bt, nc, nj),
        in_specs=[wide, wide, wide, wide, narrow, narrow,
                  pl.BlockSpec((N, d_block), lambda i, k, j: (0, j)),
                  pl.BlockSpec((1, d_block), lambda i, k, j: (0, j)),
                  pl.BlockSpec((1, 1, N, d_block),
                               lambda i, k, j: (i, nc - 1 - k, 0, j))],
        # dA and dD: one block a batch entry, in VMEM through all of its
        # grid steps and summed into there
        out_specs=[wide, wide, wide, part, part,
                   pl.BlockSpec((1, nj, N, d_block),
                                lambda i, k, j: (i, 0, 0, 0)),
                   pl.BlockSpec((1, nj, 1, d_block),
                                lambda i, k, j: (i, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((Bt, Tp, Dp), u.dtype),
                   jax.ShapeDtypeStruct((Bt, Tp, Dp), dt.dtype),
                   jax.ShapeDtypeStruct((Bt, Tp, Dp), z.dtype),
                   jax.ShapeDtypeStruct((Bt, nj, nc * tiles, N, _LANES),
                                        _F32),
                   jax.ShapeDtypeStruct((Bt, nj, nc * tiles, N, _LANES),
                                        _F32),
                   jax.ShapeDtypeStruct((Bt, nj, N, d_block), _F32),
                   jax.ShapeDtypeStruct((Bt, nj, 1, d_block), _F32)],
        scratch_shapes=[pltpu.VMEM((nj, N, d_block), _F32),
                        states, states,
                        pltpu.VMEM((3, groups, _ROWS, _LANES), _F32),
                        pltpu.VMEM((groups, N * (8 + _PAD), _LANES), _F32),
                        spread, spread, by_row, by_row],
        cost_estimate=pl.CostEstimate(
            flops=25 * Bt * Tp * Dp * N,
            bytes_accessed=Bt * Tp * Dp * (6 * u.dtype.itemsize + 8),
            transcendentals=Bt * Tp * Dp * (2 * N + 1)),
        interpret=interpret,
        compiler_params=_compiler_params(),
    )
    with jax.named_scope("ssm_scan_bwd"):
        du, ddt, dz, db, dc, da, dd = call(u, dt, z, do, b, c, a, d, bounds)
    return (du, ddt, _channel_major(da),
            _time_major(db, chunk).astype(b.dtype),
            _time_major(dc, chunk).astype(c.dtype), _channel_major(dd), dz)


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

def selective_scan_lax(u, delta, A, B, C, D, z, chunk=None):
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
    as [batch, T, Di] in ``u``'s type.  With ``interpret=None`` by
    ``common.kernel_impl``: the Pallas kernels above (differentiable in
    every operand through their own backward kernel) or
    :func:`selective_scan_lax`.  ``interpret=True`` / ``False`` force the
    kernels, interpreted or compiled.  ``chunk`` / ``d_block``
    override what ``_choose_blocks`` takes from the shape (a chunk is a
    multiple of 16, and of 128 where it is longer).
    """
    if interpret is None:
        impl = kernel_impl("selective_scan")
        if impl == "fallback":
            return selective_scan_lax(u, delta, A, B, C, D, z, chunk=chunk)
        interpret = impl == "interpret"
    Bt, T, Di = u.shape
    N = A.shape[1]
    auto = _choose_blocks(T, Di, N)
    chunk, d_block = chunk or auto[0], d_block or auto[1]
    _check_chunk(chunk)
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

