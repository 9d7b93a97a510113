"""The expert layer's row gathers as Pallas kernels
(``ops/pallas/moe_gather.py``), through the Pallas interpreter: each form
against its lax form (bit for bit where no sum is involved), ``jax.grad``
through ``expert_layer`` against the layer's lax forms, and the rows the
kernels leave unwritten poisoned with NaN."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops.pallas import moe_gather as mg
from mxnet_tpu.parallel import moe


def sorted_pairs(S, k, live, seed=0):
    """``(order, inverse)`` of ``S k`` pairs whose first ``live`` in sorted
    order are the held experts'."""
    rng = np.random.RandomState(seed)
    held = np.zeros(S * k, bool)
    held[rng.permutation(S * k)[:live]] = True
    order = np.argsort(~held, kind="stable").astype(np.int32)
    return jnp.asarray(order), jnp.asarray(np.argsort(order).astype(np.int32))


# (tokens, slots, width, dtype, live): the live count none, all, on a
# block's boundary (blocks of 256 rows at 512 pairs) and off it
FORMS = [
    pytest.param(32, 1, 2048, jnp.bfloat16, 20, id="k1_e2048"),
    pytest.param(64, 4, 2048, jnp.bfloat16, 0, id="k4_live_none"),
    pytest.param(64, 4, 2048, jnp.bfloat16, 256, id="k4_live_on_a_block"),
    pytest.param(64, 4, 2048, jnp.bfloat16, 200, id="k4_live_off_a_block"),
    pytest.param(32, 6, 2560, jnp.bfloat16, 192, id="k6_e2560_all_live"),
    pytest.param(32, 6, 2560, jnp.float32, 77, id="k6_e2560_f32"),
]


@pytest.mark.parametrize("S,k,E,dtype,live", FORMS)
def test_each_form_is_its_lax_form(S, k, E, dtype, live):
    P = S * k
    order, inverse = sorted_pairs(S, k, live)
    ks = jax.random.split(jax.random.PRNGKey(live), 4)
    src = jax.random.normal(ks[0], (S, E)).astype(dtype)
    # the down product's rows: zero past the live ones
    out = (jax.random.normal(ks[1], (P, E))
           * (jnp.arange(P) < live)[:, None]).astype(dtype)
    weights = jax.random.uniform(ks[2], (S, k))
    g = jax.random.normal(ks[3], (S, E)).astype(dtype)
    count = jnp.full((1,), live, jnp.int32)
    idx = order // k

    rows = mg.sorted_rows(src, idx, count, interpret=True)
    np.testing.assert_array_equal(np.asarray(rows[:live], np.float32),
                                  np.asarray(src[idx][:live], np.float32))

    for w in (weights, None):
        got = mg.slot_sum(out, inverse, count, w, k, interpret=True)
        want = moe._sum_slots(moe._slots(out, inverse, k), w).astype(dtype)
        assert got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=1e-2 if dtype == jnp.bfloat16 else 1e-6,
                                   atol=1e-6)

    w_rows = weights.reshape(-1)[order]
    d_out, d_w = mg.sorted_rows_grad(g, idx, count, out, w_rows,
                                     interpret=True)
    g_rows = g[idx].astype(jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(d_out[:live], np.float32),
        np.asarray((w_rows[:, None] * g_rows).astype(dtype)[:live],
                   np.float32))
    np.testing.assert_allclose(
        np.asarray(d_w[:live]),
        np.asarray(jnp.sum(out.astype(jnp.float32) * g_rows, -1)[:live]),
        rtol=1e-5, atol=1e-4)


# (tokens, slots, width, held of 8 experts, the routing's kind)
LAYERS = [
    pytest.param(64, 1, 2048, 2, "random", id="k1"),
    pytest.param(64, 4, 2048, 2, "random", id="k4"),
    pytest.param(32, 6, 2560, 3, "random", id="k6_e2560"),
    pytest.param(64, 4, 2048, 2, "none_held", id="k4_live_none"),
    pytest.param(64, 4, 2048, 8, "random", id="k4_all_live"),
    pytest.param(64, 4, 2048, 2, "on_a_block", id="k4_live_on_a_block"),
]
F, N = 64, 8


def layer_case(S, k, E, held, kind, seed=0):
    """Inputs of ``expert_layer`` with the routing given: ``(x, weights,
    experts, w_up, w_gate, w_down, held ids)``."""
    rng = np.random.RandomState(seed)
    experts = np.stack([rng.permutation(N)[:k] for _ in range(S)])
    if kind == "none_held":                 # every slot on an absent expert
        experts = np.stack([rng.permutation(np.arange(held, N))[:k]
                            for _ in range(S)])
    if kind == "on_a_block":                # the first 256 pairs held
        experts = np.where(np.arange(S * k).reshape(S, k) < 256,
                           np.arange(S * k).reshape(S, k) % held,
                           held + np.arange(S * k).reshape(S, k) % (N - held))
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (1, S, E)).astype(jnp.bfloat16)
    weights = jax.nn.softmax(jax.random.normal(ks[1], (S, k)), axis=-1)
    w_up, w_gate = ((0.05 * jax.random.normal(kk, (held, E, F))
                     ).astype(jnp.bfloat16) for kk in ks[2:4])
    w_down = (0.05 * jax.random.normal(ks[4], (held, F, E))
              ).astype(jnp.bfloat16)
    return (x, weights, jnp.asarray(experts, jnp.int32), w_up, w_gate,
            w_down, tuple(range(held)))


def layer_loss(S, k, E, experts, held):
    probe = jax.random.normal(jax.random.PRNGKey(9), (1, S, E))
    router_w = jnp.zeros((E, N), jnp.bfloat16)

    def loss(x, weights, w_up, w_gate, w_down):
        y, _aux, live = moe.expert_layer(
            x, router_w, w_up, w_down, w_gate, top_k=k, experts_held=held,
            routing=(weights, experts, jnp.float32(0)))
        return jnp.sum(y.astype(jnp.float32) * probe), live
    return loss


def grads(loss, args):
    return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(5)),
                                      has_aux=True))(*args)


@pytest.mark.parametrize("S,k,E,held,kind", LAYERS)
def test_the_layers_gradient_is_its_lax_forms(S, k, E, held, kind,
                                              monkeypatch):
    x, weights, experts, w_up, w_gate, w_down, ids = layer_case(
        S, k, E, held, kind)
    loss = layer_loss(S, k, E, experts, ids)
    args = (x, weights, w_up, w_gate, w_down)
    reg = telemetry.registry()
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    before = reg.counter("pallas.select.moe_gather.interpret").value
    (value, live), got = grads(loss, args)
    assert reg.counter("pallas.select.moe_gather.interpret").value > before
    monkeypatch.setenv("MXTPU_PALLAS", "off")
    (value0, live0), want = grads(loss, args)
    assert float(live) == float(live0)
    if kind == "none_held":
        assert float(live) == 0
    if kind == "on_a_block":
        assert float(live) == 256
    if held == N:
        assert float(live) == S * k
    np.testing.assert_allclose(float(value), float(value0), rtol=1e-3)
    for name, a, b in zip(("x", "weights", "w_up", "w_gate", "w_down"),
                          got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all(), name
        top = max(float(np.abs(b).max()), 1e-30)
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-2 * top,
                                   err_msg=name)


def test_rows_left_unwritten_reach_no_value(monkeypatch):
    """Every row past the live count of what the kernels hand on -- the
    dispatch's rows, ``d_out``, ``d_w`` -- poisoned with NaN: the grouped
    product skips or masks them and ``d_w`` is read by ``inverse < live``,
    so the loss and every gradient are finite and what they were."""
    S, k, E, held = 64, 4, 2048, 2
    x, weights, experts, w_up, w_gate, w_down, ids = layer_case(
        S, k, E, held, "random", seed=3)
    loss = layer_loss(S, k, E, experts, ids)
    args = (x, weights, w_up, w_gate, w_down)
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    (value, live), want = grads(loss, args)
    assert 0 < float(live) < S * k

    def poison(a, live):
        rows = jnp.arange(a.shape[0]).reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(rows < live[0], a, jnp.nan).astype(a.dtype)

    rows, grad = mg.sorted_rows, mg.sorted_rows_grad
    monkeypatch.setattr(mg, "sorted_rows", lambda src, idx, live, **kw:
                        poison(rows(src, idx, live, **kw), live))
    monkeypatch.setattr(mg, "sorted_rows_grad", lambda g, idx, live, *a, **kw:
                        tuple(poison(v, live) for v in grad(g, idx, live,
                                                            *a, **kw)))
    (value1, _live), got = grads(loss, args)
    assert float(value1) == float(value)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_the_kernels_take_only_shapes_mosaic_can_tile(monkeypatch):
    """``select``: a bfloat16 row whose halves are not whole 128-lane
    vectors, a token count no block divides or another type go to the lax
    forms, and on the chip so does a layer whose ``[P, E]`` buffer fits
    the fast memory (the deepseek cell's); each answer counted once."""
    reg = telemetry.registry()

    def counts():
        return {i: reg.counter("pallas.select.moe_gather." + i).value
                for i in ("pallas", "fallback")}

    monkeypatch.setenv("MXTPU_PALLAS", "auto")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = counts()
    assert mg.select(32768, 4, 2048, jnp.bfloat16) == "pallas"
    assert mg.select(16384, 6, 2560, jnp.bfloat16) == "pallas"
    assert mg.select(4096, 6, 2048, jnp.bfloat16) == "fallback"
    assert mg.select(16384, 6, 2176, jnp.bfloat16) == "fallback"
    assert mg.select(16388, 6, 2048, jnp.bfloat16) == "fallback"
    assert mg.select(16384, 6, 2048, jnp.float16) == "fallback"
    after = counts()
    assert (after["pallas"] - before["pallas"],
            after["fallback"] - before["fallback"]) == (2, 4)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert mg.select(32768, 4, 2048, jnp.bfloat16) == "fallback"
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    assert mg.select(64, 4, 32, jnp.float32) == "interpret"


def test_the_products_rows_past_the_groups_are_read_by_nothing(monkeypatch):
    """With the gathers' kernels the grouped products leave their rows past
    the groups unwritten (``rows_past_groups="unread"``: NaN from the
    interpreter) in the forward and in ``dx``; with the lax gathers, whose
    combine sums every slot's row, they write them as zero.  Three of four
    pairs on absent experts: ``y`` and the gradients of ``x``, the router
    and the three matrices are finite and the lax layer's either way."""
    S, k, E, held = 64, 4, 256, 2
    x, _w, _e, w_up, w_gate, w_down, ids = layer_case(S, k, E, held,
                                                      "random", seed=5)
    router_w = (0.5 * jax.random.normal(jax.random.PRNGKey(7), (E, N))
                ).astype(jnp.bfloat16)
    probe = jax.random.normal(jax.random.PRNGKey(9), (1, S, E))

    def loss(x, router_w, w_up, w_gate, w_down):
        y, _aux, live = moe.expert_layer(x, router_w, w_up, w_down, w_gate,
                                         top_k=k, experts_held=ids)
        return jnp.sum(y.astype(jnp.float32) * probe), (y, live)

    args = (x, router_w, w_up, w_gate, w_down)
    reg = telemetry.registry()

    def run():
        names = ["pallas.gmm.rows_past_groups." + r
                 for r in ("zero", "unread")]
        before = [reg.counter(n).value for n in names]
        (_value, (y, live)), g = grads(loss, args)
        counted = tuple(reg.counter(n).value - b
                        for n, b in zip(names, before))
        return y, live, g, counted

    monkeypatch.setenv("MXTPU_PALLAS", "off")
    want_y, want_live, want, counted = run()
    assert counted == (0, 0)                 # the lax product
    assert 0 < float(want_live) <= S * k // 2
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    kernels = run()
    # the lax gathers with the kernels' products: the deepseek cell's path
    monkeypatch.setattr(moe.moe_gather, "select", lambda *a: "fallback")
    lax_gathers = run()
    # three products, each a forward and a dx
    assert kernels[3] == (0, 6) and lax_gathers[3] == (6, 0)
    for y, live, got, _counted in (kernels, lax_gathers):
        assert float(live) == float(want_live)
        y = np.asarray(y, np.float32)
        assert np.isfinite(y).all()
        np.testing.assert_allclose(y, np.asarray(want_y, np.float32),
                                   rtol=0, atol=2e-2 * np.abs(y).max())
        for name, a, b in zip(("x", "router", "w_up", "w_gate", "w_down"),
                              got, want):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert np.isfinite(a).all(), name
            top = max(float(np.abs(b).max()), 1e-30)
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-2 * top,
                                       err_msg=name)
