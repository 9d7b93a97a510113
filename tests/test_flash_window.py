"""``flash_attention(window=W)``: the three kernels through the Pallas
interpreter against an explicitly masked dense attention (query ``t`` sees
the keys ``0 <= t - j < W``), output and the three gradients; the lax forms
behind ``kernel_impl``; the telemetry of the band; and what a window that
reaches over the whole sequence compiles to."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops.pallas import flash_attention
from mxnet_tpu.ops.pallas.flash_attention import (_inner_axis, _inner_map,
                                                  _note_tiles)
from mxnet_tpu.parallel.ring_attention import blockwise_attention

# the package's attribute of that name is the function
fa = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


def masked_dense(q, k, v, window):
    """float32, the mask written out: ``0 <= t - j < window``."""
    T = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
    gap = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    s = jnp.where((gap >= 0) & (gap < window), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def both(T, window, bq, bk, fn):
    q, k, v = (_rand(i, (2, T, 2, 32)) for i in range(3))
    w = _rand(3, (2, T, 2, 32))

    def grads(f):
        return jax.value_and_grad(
            lambda q, k, v: (f(q, k, v) * w).sum(), argnums=(0, 1, 2))(q, k, v)

    got = (fn(q, k, v), *grads(fn)[1])
    ref = lambda q, k, v: masked_dense(q, k, v, window)
    want = (ref(q, k, v), *grads(ref)[1])
    return got, want


# (T, W, block_q, block_k): a window under a tile; between tiles (not a
# multiple of either side); a tile wide; T not a multiple of the tile (the
# last tile holds padded keys *and* the band's edge); unequal sides both
# ways; one position; unequal sides that pad T to different lengths
CASES = [(128, 5, 32, 32), (128, 40, 32, 32), (128, 32, 32, 32),
         (100, 40, 32, 32), (128, 48, 32, 16), (128, 48, 16, 32),
         (128, 1, 32, 32), (100, 70, 32, 32), (100, 48, 32, 16)]


@pytest.mark.parametrize("T,W,bq,bk", CASES)
def test_the_kernels_agree_with_an_explicit_mask(T, W, bq, bk):
    got, want = both(T, W, bq, bk, lambda q, k, v: flash_attention(
        q, k, v, window=W, block_q=bq, block_k=bk, interpret=True))
    for a, b, name in zip(got, want, ("o", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=name)


@pytest.mark.parametrize("T,W", [(128, 40), (100, 7), (700, 300)])
def test_the_lax_form_takes_the_same_mask(T, W):
    got, want = both(T, W, None, None, lambda q, k, v: blockwise_attention(
        q, k, v, causal=True, window=W))
    for a, b, name in zip(got, want, ("o", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=name)


@pytest.mark.parametrize("W", [128, 129, 4096])
def test_a_window_over_the_whole_sequence_is_the_causal_kernel(W):
    """To the bit, forward and backward, and by the program traced."""
    q, k, v = (_rand(i, (1, 128, 2, 32)) for i in range(3))

    def f(window):
        return jax.value_and_grad(lambda q, k, v: flash_attention(
            q, k, v, window=window, block_q=32, block_k=32,
            interpret=True).sum(), argnums=(0, 1, 2))

    got, want = f(W)(q, k, v), f(None)(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert str(jax.make_jaxpr(f(W))(q, k, v)) == str(
        jax.make_jaxpr(f(None))(q, k, v))


def test_no_window_traces_the_kernels_it_traced_before():
    """``window=None`` is no branch taken at run time: the traced program
    names no window, and the calls keep their names."""
    q = _rand(0, (1, 128, 2, 32))
    text = str(jax.make_jaxpr(jax.grad(lambda q: flash_attention(
        q, q, q, block_q=32, block_k=32, interpret=True).sum()))(q))
    assert "window" not in text
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert name in text
    with_w = str(jax.make_jaxpr(jax.grad(lambda q: flash_attention(
        q, q, q, block_q=32, block_k=32, window=40,
        interpret=True).sum()))(q))
    for name in ("flash_fwd_window", "flash_dq_window", "flash_dkv_window"):
        assert name in with_w


def _touches(qi, ki, W, bq, bk):
    """``_visit``'s two conditions: some pair of the tile is in the band."""
    return ki * bk <= qi * bq + bq - 1 and ki * bk + bk - 1 + W > qi * bq


def _runs(T, W, bq, bk, inner_is_k):
    """By brute force: the inner tiles each outer tile's band touches."""
    nq, nk = T // bq, T // bk
    if inner_is_k:
        return [[ki for ki in range(nk) if _touches(qi, ki, W, bq, bk)]
                for qi in range(nq)]
    return [[qi for qi in range(nq) if _touches(qi, ki, W, bq, bk)]
            for ki in range(nk)]


LONG = [(16384, 4096, 1024, 1024), (16384, 4096, 512, 1024)]


def _padded(T, bq, bk):
    """``T`` as ``_attend`` pads it: to whole tiles of the larger side."""
    return -(-T // max(bq, bk)) * max(bq, bk)


@pytest.mark.parametrize("T,W,bq,bk", LONG + [(128, 40, 32, 32),
                                              (128, 48, 16, 32),
                                              (128, 1, 32, 32)])
def test_a_tile_outside_the_band_is_neither_fetched_nor_run(T, W, bq, bk):
    """The index maps, the kernels' tile of a step and ``_visit``'s condition
    by brute force over the short grid: every tile that touches the band is
    visited exactly once and in rising order, every other step runs nothing
    and keeps the index of a tile that does (no DMA), both ways round."""
    nq, nk = T // bq, T // bk
    for inner_is_k, n_outer, n_inner in ((True, nq, nk), (False, nk, nq)):
        steps, first = _inner_axis(bq, bk, n_outer, n_inner, inner_is_k, W)
        index_map = _inner_map(True, bq, bk, n_inner, inner_is_k, W, first)
        for i, run in enumerate(_runs(T, W, bq, bk, inner_is_k)):
            assert run == list(range(run[0], run[-1] + 1))
            tiles = [int(first(i)) + j for j in range(steps)]
            visited = [t for t in tiles if
                       (_touches(i, t, W, bq, bk) if inner_is_k
                        else _touches(t, i, W, bq, bk))]
            assert visited == run
            for j, t in enumerate(tiles):
                fetched = int(index_map(0, 0, i, j)[2])
                assert fetched == (t if t in run else
                                   run[0] if t < run[0] else run[-1])
    # the pairs of the band all lie in tiles that run
    if T <= 128:
        gap = np.arange(T)[:, None] - np.arange(T)[None, :]
        for t, j in zip(*np.nonzero((gap >= 0) & (gap < W))):
            assert _touches(t // bq, j // bk, W, bq, bk)


@pytest.mark.parametrize("T,W,bq,bk", CASES + LONG)
def test_the_inner_axis_is_as_long_as_the_longest_run(T, W, bq, bk):
    T = _padded(T, bq, bk)
    nq, nk = T // bq, T // bk
    for inner_is_k, n_outer, n_inner in ((True, nq, nk), (False, nk, nq)):
        steps, _ = _inner_axis(bq, bk, n_outer, n_inner, inner_is_k, W)
        assert steps == max(map(len, _runs(T, W, bq, bk, inner_is_k)))
        assert steps <= n_inner
        for whole in (T, T + 1, 4 * T):        # the whole causal prefix
            assert _inner_axis(bq, bk, n_outer, n_inner, inner_is_k,
                               whole)[0] == n_inner
        assert _inner_axis(bq, bk, n_outer, n_inner, inner_is_k,
                           None) == (n_inner, None)
    if (T, W, bq, bk) == LONG[0]:
        assert steps == 5


@pytest.mark.parametrize("T,W,bq,bk", CASES)
def test_the_short_axis_sums_what_the_long_axis_summed(T, W, bq, bk,
                                                       monkeypatch):
    """Output and the three gradients to the last bit against the schedule
    before: an inner axis as long as the sequence, step ``j`` tile ``j``,
    the index held between the band's ends — the same pairs enter the same
    sums in the same order."""
    def fn(q, k, v):
        return flash_attention(q, k, v, window=W, block_q=bq, block_k=bk,
                               interpret=True)

    got, _ = both(T, W, bq, bk, fn)

    def long_axis(block_q, block_k, n_outer, n_inner, inner_is_k, window):
        return n_inner, lambda i: 0

    grids = []
    real_call = fa.pl.pallas_call
    monkeypatch.setattr(fa, "_inner_axis", long_axis)
    monkeypatch.setattr(fa.pl, "pallas_call", lambda *a, **kw: (
        grids.append(kw["grid"]), real_call(*a, **kw))[1])
    want, _ = both(T, W, bq, bk, fn)
    Tp = _padded(T, bq, bk)
    assert {g[2] * g[3] for g in grids} == {(Tp // bq) * (Tp // bk)}
    for a, b, name in zip(got, want, ("o", "dq", "dk", "dv")):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


def test_the_band_is_counted_beside_the_causal_share():
    reg = telemetry.registry()
    before = reg.snapshot()["counters"].get("pallas.flash.window.fwd.4096", 0)
    _note_tiles("fwd", 1024, 1024, 16, 16, True, 4096)
    snap = reg.snapshot()
    assert snap["counters"]["pallas.flash.window.fwd.4096"] == before + 1
    # 16 x 16 tiles of 1024: 136 on or under the diagonal; the band of
    # 4,096 touches the diagonal tile and four behind it
    assert snap["gauges"]["pallas.flash.causal_tiles_run_share"] == \
        pytest.approx(136 / 256)
    assert snap["gauges"]["pallas.flash.band_tiles_run_share"] == \
        pytest.approx((1 + 2 + 3 + 4 + 12 * 5) / 256)
    _note_tiles("fwd", 1024, 1024, 16, 16, True)
    assert reg.snapshot()["counters"]["pallas.flash.window.fwd.4096"] == \
        before + 1


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
def test_the_inner_axis_is_counted_and_the_grid_steps_that_run(kernel):
    reg = telemetry.registry()

    def count(name):
        return reg.snapshot()["counters"].get(
            "pallas.flash.inner_steps.%s.%s" % (kernel, name), 0)

    short, whole = count("5of16"), count("16of16")
    _note_tiles(kernel, 1024, 1024, 16, 16, True, 4096)
    assert (count("5of16"), count("16of16")) == (short + 1, whole)
    # 16 outer tiles of 5 steps; 1 + 2 + 3 + 4 + 12 x 5 = 70 of the 80 run
    assert reg.snapshot()["gauges"]["pallas.flash.grid_steps_run_share"] \
        == pytest.approx(0.875)
    _note_tiles(kernel, 1024, 1024, 16, 16, True)
    assert (count("5of16"), count("16of16")) == (short + 1, whole + 1)
    assert reg.snapshot()["gauges"]["pallas.flash.grid_steps_run_share"] \
        == pytest.approx(136 / 256)
    # the shares of the nq x nk tiles keep their meaning
    assert reg.snapshot()["gauges"]["pallas.flash.band_tiles_run_share"] \
        == pytest.approx(70 / 256)


def test_a_window_is_over_a_causal_self_attention():
    q = _rand(0, (1, 64, 1, 32))
    with pytest.raises(AssertionError, match="window"):
        flash_attention(q, q, q, causal=False, window=8, interpret=True)
