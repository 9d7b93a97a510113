"""Grouped matrix product as Pallas TPU kernels (forward, ``dx``, ``dW``).

``grouped_matmul(lhs, rhs, group_sizes)``: the rows of ``lhs`` [M, K] lie
group after group (``group_sizes`` [G], summing to at most M), and group
``g``'s rows are multiplied by its own matrix ``rhs[g]`` [K, N] -- the
product of an expert layer whose (token, slot) pairs were sorted by expert
(`parallel/moe.py`).  What the rows past the last group hold is the
caller's choice, ``rows_past_groups``: ``"zero"`` (the default, what
``lax.ragged_dot`` states) or ``"unread"``, for a caller that reads no such
row of the result or of ``dx``.  Design:

* the row axis is cut into tiles of ``tm`` rows; a tile a group boundary
  crosses is visited once for each group it holds, under a row mask, so
  group sizes need not be multiples of anything and a group may be empty;
* which (group, row tile) a grid step works on is computed in lax from
  ``group_sizes`` (``_schedule``) and handed to the kernels as scalar
  prefetch; the step axis is as long as the live steps (a grid bound the
  schedule computes, at most ``M / tm + G``: every tile once, one more
  visit a boundary), so no grid step is spent on a tile nothing computes;
* rows past the last group: with ``"zero"`` forward and ``dx`` append them
  as a run of tiles of their own, each fetched and written as zero; with
  ``"unread"`` there is no such run -- the rows of the tile that holds the
  last group's end still come out zero, the tiles past it are neither read
  nor written (the expert layer with its row-gather kernels, whose
  consumers read only the groups' rows);
* forward and ``dx`` are one kernel (``dx = dy . rhs[g]^T`` contracts the
  matrices' last dimension: the block is fetched as stored and the product
  is ``a . b^T``, nothing is transposed in HBM); the whole contraction and
  the whole output width are one block where that fits, so a group's matrix
  is fetched once for all its row tiles;
* ``dW[g] = lhs_g^T . dy_g`` is a second kernel, the steps innermost, with a
  float32 accumulator that opens at a group's first tile and is stored at
  its last; an empty group is visited once, fully masked, so its ``dW`` is
  written as zero;
* operands reach the MXU in their own dtype, accumulated in float32; the
  result takes ``lhs``'s dtype, ``dW`` takes ``rhs``'s;
* every ``pallas_call`` has a ``name=`` and a ``jax.named_scope``
  (``gmm_fwd``, ``gmm_dx``, ``gmm_dw``); at trace time the counter
  ``pallas.gmm.tile.<kernel>.<tm>x<tk>x<tn>`` records the tiles and
  ``pallas.gmm.rows_past_groups.<zero|unread>`` each forward and ``dx``,
  ``pallas.gmm.grid.live`` each kernel whose step axis is the live steps;
* called with ``interpret=None`` the entry point asks
  ``common.kernel_impl``: the kernels, or ``grouped_matmul_lax`` (the same
  mathematics group by group in lax, which GSPMD shards freely);
  ``interpret=True`` runs the kernels through the Pallas interpreter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import _round_up, kernel_impl

__all__ = ["grouped_matmul", "grouped_matmul_lax"]

# rows of a tile.  A tile is computed whole for every group it holds, so a
# narrow tile wastes less at a group's boundary, and the MXU runs fuller on a
# wide one.  On the v5e at 24,576 x 2048 x 1408 in bfloat16, 32 groups of
# about 384 rows: forward 0.97 / 0.99 / 1.22 ms and forward + dx + dW 2.85 /
# 2.91 / 3.52 ms at 128 / 256 / 512 rows (PERF.md, PR 32).
_TM = 128
# the reckoned working set of a grid step stays under the budget; the call
# asks for the limit (the v5e has 128 MiB of VMEM)
_VMEM_BUDGET = 28 * 1024 * 1024
_VMEM_LIMIT = 64 * 1024 * 1024
_LADDER = (2048, 1024, 512, 256, 128)


def _group_of_row(group_sizes, M):
    """[M] the group each row belongs to; ``G`` for rows past the last."""
    return jnp.searchsorted(jnp.cumsum(group_sizes.astype(jnp.int32)),
                            jnp.arange(M, dtype=jnp.int32), side="right")


@jax.custom_vjp
def grouped_matmul_lax(lhs, rhs, group_sizes):
    """The same product in lax: one group after another, its rows picked
    by a mask, float32 accumulation, ``lhs``'s dtype.  ``G`` times the
    operations of the kernels: what the CPU and a mesh get.  Not
    ``lax.ragged_dot``: on the TPU (libtpu 0.0.34, v5e) its instruction
    leaves the gradient's rows past the last group unwritten and misplaces
    the forward's rows behind a leading empty group (PERF.md, PR 32)."""
    group = _group_of_row(group_sizes, lhs.shape[0])

    def add(acc, gw):
        g, w = gw
        rows = jnp.where((group == g)[:, None], lhs, jnp.zeros_like(lhs))
        return acc + jnp.dot(rows, w, preferred_element_type=jnp.float32), \
            None

    out = lax.scan(add, jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32),
                   (jnp.arange(rhs.shape[0]), rhs))[0]
    return out.astype(lhs.dtype)


def _lax_fwd(lhs, rhs, group_sizes):
    return grouped_matmul_lax(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _lax_bwd(res, dy):
    lhs, rhs, group_sizes = res
    group = _group_of_row(group_sizes, lhs.shape[0])

    def one(dx, gw):
        g, w = gw
        mine = (group == g)[:, None]
        dx = dx + jnp.where(mine, jnp.dot(
            dy, w.T, preferred_element_type=jnp.float32), 0.0)
        dw = jnp.dot(jnp.where(mine, lhs, jnp.zeros_like(lhs)).T, dy,
                     preferred_element_type=jnp.float32)
        return dx, dw.astype(rhs.dtype)

    dx, dw = lax.scan(one, jnp.zeros(lhs.shape, jnp.float32),
                      (jnp.arange(rhs.shape[0]), rhs))
    return dx.astype(lhs.dtype), dw, None


grouped_matmul_lax.defvjp(_lax_fwd, _lax_bwd)


# ---------------------------------------------------------------------------
# the schedule: tiles from the shape, steps from the group sizes
# ---------------------------------------------------------------------------

def _sides(n):
    """Block sizes a dimension of ``n`` may be cut into, widest first: the
    whole of it, then the rungs that divide it."""
    return [n] + [r for r in _LADDER if r < n and n % r == 0]


def _choose_tiles(kernel, M, K, N, itemsize):
    """``(tm, tk, tn)``.  One row tile of ``_round_up(M, 8)`` up to ``_TM``
    rows, else ``_TM``; of the other two sides the wider steps down a rung
    while the step's working set (operand and result tiles twice, the
    float32 accumulator and the product it adds) is over the budget."""
    tm = min(_TM, _round_up(M, 8))
    ks, ns = _sides(K), _sides(N)

    def working_set(tk, tn):
        if kernel == "gmm_dw":                 # lhs dy -> dW; acc [tk, tn]
            tiles = tm * tk + tm * tn + tk * tn
            acc = tk * tn
        else:                                  # lhs rhs -> out; acc [tm, tn]
            tiles = tm * tk + tk * tn + tm * tn
            acc = tm * tn
        return 2 * tiles * itemsize + 2 * acc * 4

    while working_set(ks[0], ns[0]) > _VMEM_BUDGET and (
            len(ks) > 1 or len(ns) > 1):
        k_steps = len(ks) > 1 and (ks[0] >= ns[0] or len(ns) == 1)
        (ks if k_steps else ns).pop(0)
    from ... import telemetry as _telemetry
    _telemetry.registry().counter(
        "pallas.gmm.tile.%s.%dx%dx%d" % (kernel, tm, ks[0], ns[0])).inc()
    return tm, ks[0], ns[0]


def _schedule(group_sizes, M, tm, tail, visit_empty):
    """The step axis.  Returns ``(offsets, group_of, tile_of, n_steps)``:
    ``offsets`` [G' + 1] the first row of each group and the end of the
    last, ``group_of`` / ``tile_of`` [S] the group and the row tile of each
    step (their static length ``S`` is the most there can be; the grid
    takes the first ``n_steps``), ``n_steps`` [1] how many steps are live.
    ``tail`` appends the rows past the last group as a group of their own
    (index G), so that every row tile is visited; ``visit_empty`` gives an
    empty group one (fully masked) step."""
    sizes = group_sizes.astype(jnp.int32)
    tiles_m = M // tm
    if tail:
        sizes = jnp.concatenate([sizes, (M - jnp.sum(sizes))[None]])
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    n_tiles = jnp.where(sizes > 0, (ends + tm - 1) // tm - first,
                        1 if visit_empty else 0)
    cum = jnp.cumsum(n_tiles)
    n_steps = cum[-1]
    S = tiles_m + sizes.shape[0] - 1
    # a step past the live ones repeats the last live one's indices
    s = jnp.clip(jnp.arange(S, dtype=jnp.int32), 0,
                 jnp.maximum(n_steps - 1, 0))
    group_of = jnp.minimum(jnp.searchsorted(cum, s, side="right"),
                           sizes.shape[0] - 1).astype(jnp.int32)
    tile_of = first[group_of] + s - (cum[group_of] - n_tiles[group_of])
    tile_of = jnp.clip(tile_of, 0, tiles_m - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group_of, tile_of, n_steps[None].astype(jnp.int32)


def _steps(meta):
    """The grid's step extent, a bound computed on the device: the live
    steps, at least one (with none, that step is skipped)."""
    from ... import telemetry as _telemetry
    _telemetry.registry().counter("pallas.gmm.grid.live").inc()
    return jnp.maximum(meta[3][0], 1)


def _row_mask(offsets, group, tile, tm, shape):
    """[tm, n] True on the rows of ``tile`` that belong to ``group``."""
    rows = tile * tm + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= offsets[group]) & (rows < offsets[group + 1])


# ---------------------------------------------------------------------------
# forward and dx: out[rows of g] = lhs[rows of g] . rhs[g] (or . rhs[g]^T)
# ---------------------------------------------------------------------------

def _gmm_kernel(offsets, group_of, tile_of, n_steps, lhs_ref, rhs_ref,
                out_ref, acc_ref, *, tm, n_groups, transpose_rhs):
    s = pl.program_id(1)
    k_i = pl.program_id(2)
    nk = pl.num_programs(2)
    group, tile = group_of[s], tile_of[s]
    live = s < n_steps[0]

    @pl.when(live & (k_i == 0))
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # with zeros past the groups those rows are a run of tiles (group G)
    # with nothing to add
    @pl.when(live & (group < n_groups))
    def _():
        contract = ((1,), (1,)) if transpose_rhs else ((1,), (0,))
        acc_ref[:] += lax.dot_general(
            lhs_ref[:], rhs_ref[:], (contract, ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(live & (k_i == nk - 1))
    def _():
        mask = _row_mask(offsets, group, tile, tm, acc_ref.shape)
        # a tile's first visit finds whatever the buffer held: its rows of
        # other groups are written by their own visits, the rest as zero
        first_visit = (s == 0) | (tile_of[jnp.maximum(s - 1, 0)] != tile)
        rest = jnp.where(first_visit, 0.0, out_ref[:].astype(jnp.float32))
        out_ref[:] = jnp.where(mask, acc_ref[:], rest).astype(out_ref.dtype)


def _gmm(lhs, rhs, group_sizes, transpose_rhs, interpret, rows_past_groups):
    """lhs [M, K] by rhs [G, K, N] (``transpose_rhs``: [G, N, K]); rows
    past the last group zero, or with ``"unread"`` zero only in the tile
    that holds the last group's end."""
    from ... import telemetry as _telemetry
    M, K = lhs.shape
    G = rhs.shape[0]
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    kernel = "gmm_dx" if transpose_rhs else "gmm_fwd"
    tm, tk, tn = _choose_tiles(kernel, M, K, N, jnp.dtype(lhs.dtype).itemsize)
    _telemetry.registry().counter(
        "pallas.gmm.rows_past_groups." + rows_past_groups).inc()
    pad = _round_up(M, tm) - M
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    Mp = M + pad
    meta = _schedule(group_sizes, Mp, tm, tail=rows_past_groups == "zero",
                     visit_empty=False)
    S = _steps(meta)

    def lhs_map(n_i, s, k_i, offsets, group_of, tile_of, n_steps):
        return tile_of[s], k_i

    def rhs_map(n_i, s, k_i, offsets, group_of, tile_of, n_steps):
        g = jnp.minimum(group_of[s], G - 1)
        return (g, n_i, k_i) if transpose_rhs else (g, k_i, n_i)

    def out_map(n_i, s, k_i, offsets, group_of, tile_of, n_steps):
        return tile_of[s], n_i

    call = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, n_groups=G,
                          transpose_rhs=transpose_rhs),
        name=kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(N // tn, S, K // tk),
            in_specs=[
                pl.BlockSpec((tm, tk), lhs_map),
                pl.BlockSpec((None, tn, tk) if transpose_rhs
                             else (None, tk, tn), rhs_map),
            ],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((Mp, N), lhs.dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * Mp * K * N,
            bytes_accessed=(Mp * (K + N) * lhs.dtype.itemsize
                            + G * K * N * rhs.dtype.itemsize),
            transcendentals=0),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )
    with jax.named_scope(kernel):
        out = call(*meta, lhs, rhs)
    return out[:M] if pad else out


# ---------------------------------------------------------------------------
# dW[g] = lhs[rows of g]^T . dy[rows of g]
# ---------------------------------------------------------------------------

def _dw_kernel(offsets, group_of, tile_of, n_steps, lhs_ref, dy_ref, out_ref,
               acc_ref, *, tm):
    s = pl.program_id(2)
    group, tile = group_of[s], tile_of[s]
    live = s < n_steps[0]
    last = n_steps[0] - 1
    opens = (s == 0) | (group_of[jnp.maximum(s - 1, 0)] != group)
    closes = (s == last) | (group_of[jnp.minimum(s + 1, last)] != group)

    @pl.when(live & opens)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def add(x, dy):
        acc_ref[:] += lax.dot_general(
            x, dy, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    runs = live & (offsets[group + 1] > offsets[group])
    whole = ((tile * tm >= offsets[group])
             & ((tile + 1) * tm <= offsets[group + 1]))

    @pl.when(runs & whole)
    def _():
        add(lhs_ref[:], dy_ref[:])

    # a tile a group's boundary crosses: both operands' other rows are
    # masked, so that a row no group owns may hold anything (the expert
    # layer's gathers leave rows past the live ones unwritten)
    @pl.when(runs & jnp.logical_not(whole))
    def _():
        x, dy = lhs_ref[:], dy_ref[:]
        add(jnp.where(_row_mask(offsets, group, tile, tm, x.shape), x,
                      jnp.zeros_like(x)),
            jnp.where(_row_mask(offsets, group, tile, tm, dy.shape), dy,
                      jnp.zeros_like(dy)))

    @pl.when(live & closes)
    def _():
        out_ref[:] = acc_ref[:].astype(out_ref.dtype)


def _dw(lhs, dy, group_sizes, n_groups, dtype, interpret):
    """lhs [M, K], dy [M, N] -> [G, K, N] in ``dtype``."""
    M, K = lhs.shape
    N = dy.shape[1]
    tm, tk, tn = _choose_tiles("gmm_dw", M, K, N,
                               jnp.dtype(lhs.dtype).itemsize)
    pad = _round_up(M, tm) - M
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        dy = jnp.pad(dy, ((0, pad), (0, 0)))
    meta = _schedule(group_sizes, M + pad, tm, tail=False, visit_empty=True)
    S = _steps(meta)

    def lhs_map(k_i, n_i, s, offsets, group_of, tile_of, n_steps):
        return tile_of[s], k_i

    def dy_map(k_i, n_i, s, offsets, group_of, tile_of, n_steps):
        return tile_of[s], n_i

    def out_map(k_i, n_i, s, offsets, group_of, tile_of, n_steps):
        return group_of[s], k_i, n_i

    call = pl.pallas_call(
        functools.partial(_dw_kernel, tm=tm),
        name="gmm_dw",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(K // tk, N // tn, S),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((tm, tn), dy_map)],
            out_specs=pl.BlockSpec((None, tk, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n_groups, K, N), dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * K * N,
            bytes_accessed=(M * (K + N) * lhs.dtype.itemsize
                            + n_groups * K * N * jnp.dtype(dtype).itemsize),
            transcendentals=0),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )
    with jax.named_scope("gmm_dw"):
        return call(*meta, lhs, dy)


# ---------------------------------------------------------------------------
# custom_vjp wrapper and the entry point
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped(lhs, rhs, group_sizes, interpret, rows_past_groups):
    return _gmm(lhs, rhs, group_sizes, False, interpret, rows_past_groups)


def _grouped_fwd(lhs, rhs, group_sizes, interpret, rows_past_groups):
    return (_gmm(lhs, rhs, group_sizes, False, interpret, rows_past_groups),
            (lhs, rhs, group_sizes))


def _grouped_bwd(interpret, rows_past_groups, res, dy):
    lhs, rhs, group_sizes = res
    dx = _gmm(dy, rhs, group_sizes, True, interpret, rows_past_groups)
    dw = _dw(lhs, dy, group_sizes, rhs.shape[0], rhs.dtype, interpret)
    return dx, dw, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs, rhs, group_sizes, interpret=None,
                   rows_past_groups="zero"):
    """``out[r] = lhs[r] . rhs[g(r)]`` for lhs [M, K], rhs [G, K, N] and
    ``group_sizes`` [G] (rows ``sum(sizes[:g]) .. sum(sizes[:g + 1])`` are
    group ``g``'s).  Rows past the last group are zero, in the result and
    in ``dx``; with ``rows_past_groups="unread"`` they are unspecified (the
    caller reads none of them), and the kernels neither read nor write the
    row tiles past the last group.  Differentiable in ``lhs`` and ``rhs``.
    With ``interpret=None`` the implementation is ``common.kernel_impl``'s
    answer (under a mesh the lax form, zeros past the groups either way:
    the kernels have no ``shard_map`` wrapper); ``interpret=True`` forces
    the kernels through the Pallas interpreter."""
    assert rows_past_groups in ("zero", "unread"), rows_past_groups
    if interpret is None:
        impl = kernel_impl("grouped_matmul")
        if impl in ("fallback", "sharded"):
            return grouped_matmul_lax(lhs, rhs, group_sizes)
        interpret = impl == "interpret"
    return _grouped(lhs, rhs, group_sizes.astype(jnp.int32), interpret,
                    rows_past_groups)
